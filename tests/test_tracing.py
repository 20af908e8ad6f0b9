"""Request-lifecycle tracing tests (ISSUE 8, docs/observability.md).

Covers the tentpole: RequestTrace stage ordering under concurrent
submitters through a real PredictorPool, TTFT/TPOT + decomposition
timers, the exemplar-ring bound with gauge-retracting eviction,
deadline-miss counters + budget burn, preemption/replay events on
generation pool-pressure replay, the /tracez endpoint (text + JSON),
and the disabled path (flag off: the shared no-op trace, no new
instruments, nothing recorded).
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, serving, tracing
from paddle_tpu.flags import get_flag, set_flags
from paddle_tpu.monitor import gauge_get, snapshot, stat_get, timer_get


@pytest.fixture(autouse=True)
def _fresh_rings():
    """Ring isolation (counters stay global — tests use deltas)."""
    tracing.reset()
    yield
    tracing.reset()
    set_flags({"FLAGS_request_tracing": True,
               "FLAGS_tracing_exemplars": 32})


@pytest.fixture
def model_dir(tmp_path):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [6])
        h = layers.fc(x, 16, act="relu")
        y = layers.fc(h, 3, name="out")
    exe = pt.Executor()
    exe.run(startup)
    d = str(tmp_path / "model")
    pt.io.save_inference_model(d, ["x"], [y], exe, main_program=main)
    return d


# ---------------------------------------------------------------------------
# RequestTrace core
# ---------------------------------------------------------------------------

def test_trace_ids_unique_and_stages_monotonic():
    seen = set()
    for _ in range(5):
        tr = tracing.begin("serving")
        assert tr.trace_id not in seen
        seen.add(tr.trace_id)
        for s in ("admit", "batch_join", "dispatch", "execute",
                  "fetch"):
            tr.stage(s)
        tr.finish()
    rec = tracing.recent()[-1]
    assert [s for s, _ in rec["stages"]] == [
        "submit", "admit", "batch_join", "dispatch", "execute",
        "fetch", "done"]
    offs = [t for _, t in rec["stages"]]
    assert offs == sorted(offs)
    assert rec["error"] is None


def test_finish_idempotent_and_decomposition_timers():
    c0 = stat_get("STAT_trace_completed")
    n0 = timer_get("TIMER_serving_total_us")["count"]
    tr = tracing.begin("serving")
    tr.stage("admit")
    tr.stage("batch_join")
    tr.stage("dispatch")
    tr.stage("execute")
    tr.stage("fetch")
    tr.finish()
    tr.finish()  # idempotent: no double counting
    assert stat_get("STAT_trace_completed") - c0 == 1
    assert timer_get("TIMER_serving_total_us")["count"] - n0 == 1
    # every serving interval got one sample
    for fam in ("admit", "batch_join", "dispatch", "execute",
                "fetch"):
        assert timer_get("TIMER_serving_%s_us" % fam)["count"] >= 1


def test_ttft_once_tpot_per_token():
    t0 = timer_get("TIMER_generation_ttft_us")["count"]
    p0 = timer_get("TIMER_generation_tpot_us")["count"]
    tr = tracing.begin("generation")
    tr.stage("admit")
    tr.stage("prefill_start")
    for _ in range(4):
        tr.token()
    tr.finish(finish_reason="length")
    assert timer_get("TIMER_generation_ttft_us")["count"] - t0 == 1
    assert timer_get("TIMER_generation_tpot_us")["count"] - p0 == 3
    rec = tracing.recent()[-1]
    assert rec["tokens"] == 4
    assert "first_token" in [s for s, _ in rec["stages"]]
    assert rec["ttft_us"] >= 0


def test_deadline_miss_counter_and_budget_burn():
    m0 = stat_get("STAT_serving_deadline_missed")
    b0 = stat_get("STAT_serving_budget_total_us")
    tr = tracing.begin("serving", deadline=1e-4)
    tr.stage("admit")
    time.sleep(0.005)
    tr.stage("execute")
    tr.finish()
    assert stat_get("STAT_serving_deadline_missed") - m0 == 1
    # budget burn attributed per decomposition interval
    assert stat_get("STAT_serving_budget_total_us") - b0 > 1e3
    rec = tracing.recent()[-1]
    assert rec["deadline_missed"] is True
    # a comfortable deadline does not flag
    tr2 = tracing.begin("serving", deadline=60.0)
    tr2.finish()
    assert stat_get("STAT_serving_deadline_missed") - m0 == 1
    assert tracing.recent()[-1]["deadline_missed"] is False


def test_errored_trace_counted_and_in_flight_recorder():
    from paddle_tpu import telemetry
    e0 = stat_get("STAT_trace_errored")
    tr = tracing.begin("serving")
    tr.stage("admit")
    tr.finish(error=RuntimeError("boom"))
    assert stat_get("STAT_trace_errored") - e0 == 1
    rec = tracing.recent()[-1]
    assert "boom" in rec["error"]
    # errored traces always make the exemplar ring, with a flight slice
    ex = {r["trace_id"]: r for r in tracing.exemplars()}
    assert tr.trace_id in ex
    assert "flight" in ex[tr.trace_id]
    # and land in the flight recorder keyed by trace id
    keys = [r.get("step") for r in telemetry.flight_records()]
    assert ("req:%s" % tr.trace_id) in keys


# ---------------------------------------------------------------------------
# exemplar ring: bound + gauge-retracting eviction
# ---------------------------------------------------------------------------

def test_exemplar_ring_bound_and_eviction():
    set_flags({"FLAGS_tracing_exemplars": 3})
    ids = []
    for i in range(6):
        tr = tracing.begin("serving")
        # strictly increasing totals, stamped: no sleep, so no scheduler
        # of a loaded machine can reorder them (finish() keeps a `done`
        # stage it finds)
        tr.stages.append(("done", tr.t0 + 0.004 * (i + 1)))
        tr.finish()
        ids.append(tr.trace_id)
    kept = [r["trace_id"] for r in tracing.exemplars()]
    assert len(kept) == 3
    # the fastest traces were evicted, the slowest kept
    assert set(kept) == set(ids[-3:])
    assert gauge_get("GAUGE_tracing_exemplars") == 3
    # eviction retracted the per-exemplar gauges
    from paddle_tpu.monitor import _GAUGES, _LOCK
    with _LOCK:
        for tid in ids[:3]:
            assert "GAUGE_trace_exemplar_us_%s" % tid not in _GAUGES
        for tid in ids[-3:]:
            assert "GAUGE_trace_exemplar_us_%s" % tid in _GAUGES
    assert stat_get("STAT_tracing_exemplar_evict") >= 3


def test_exemplar_ring_keeps_errored_over_fast_clean():
    set_flags({"FLAGS_tracing_exemplars": 2})
    bad = tracing.begin("serving")
    bad.finish(error=RuntimeError("keep me"))  # fast AND errored
    for i in range(4):
        tr = tracing.begin("serving")
        time.sleep(0.002)
        tr.finish()
    kept = tracing.exemplars()
    assert len(kept) == 2
    # the errored exemplar persists even though every clean trace is
    # slower; eviction prefers dropping clean ones
    assert any(r["trace_id"] == bad.trace_id for r in kept)


# ---------------------------------------------------------------------------
# disabled path
# ---------------------------------------------------------------------------

def test_flag_off_spawns_nothing_and_adds_no_instruments():
    set_flags({"FLAGS_request_tracing": False})
    names0 = {k for k in snapshot()["timers"]}
    c0 = stat_get("STAT_trace_completed")
    tr = tracing.begin("serving", deadline=0.001)
    assert tr is tracing.NOOP_TRACE
    assert tr.trace_id is None
    tr.stage("admit")
    tr.event("retry")
    tr.token()
    tr.note(rows=1)
    tr.finish(error=RuntimeError("ignored"))
    assert tr.last_stage() is None
    assert tracing.recent() == []
    assert tracing.exemplars() == []
    assert stat_get("STAT_trace_completed") == c0
    assert {k for k in snapshot()["timers"]} == names0
    payload = tracing.tracez()
    assert payload["enabled"] is False


# ---------------------------------------------------------------------------
# concurrent submitters through a real PredictorPool
# ---------------------------------------------------------------------------

def test_stage_ordering_under_concurrent_submitters(model_dir):
    from paddle_tpu.inference import Config
    T, N = 4, 10
    c0 = stat_get("STAT_trace_completed")
    n0 = stat_get("STAT_trace_nonmonotonic")
    with serving.PredictorPool(Config(model_dir), max_batch=8) as pool:
        rng = np.random.RandomState(0)
        feeds = [rng.randn(int(rng.randint(1, 5)), 6).astype(np.float32)
                 for _ in range(T * N)]

        def worker(tid):
            for i in range(tid, T * N, T):
                pool.run([feeds[i]], timeout=60.0)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(T)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    done = stat_get("STAT_trace_completed") - c0
    assert done == T * N
    assert stat_get("STAT_trace_nonmonotonic") - n0 == 0
    recs = [r for r in tracing.recent() if r["kind"] == "serving"]
    assert len(recs) >= T * N
    order = ["submit", "admit", "batch_join", "dispatch", "execute",
             "fetch", "done"]
    for rec in recs[-T * N:]:
        assert [s for s, _ in rec["stages"]] == order
        offs = [t for _, t in rec["stages"]]
        assert offs == sorted(offs)


# ---------------------------------------------------------------------------
# generation: preemption/replay events
# ---------------------------------------------------------------------------

def test_preempt_and_replay_events_on_generation_replay():
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest, init_params)
    cfg = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                        max_seq_len=32)
    params = init_params(cfg, seed=0)
    # a pool too small for both sequences' full length: the youngest
    # gets preempted mid-decode and replayed (test_generation.py's
    # eviction scenario)
    eng = GenerationEngine(cfg, params, num_blocks=10, block_size=4,
                           decode_width=2)
    reqs = [GenerationRequest(prompt=[1 + i] * 12, max_new_tokens=12,
                              request_id=i) for i in range(2)]
    results = eng.generate(reqs)
    evicted = [r for r in results if r.evictions > 0]
    assert evicted, "workload did not trigger preemption"
    by_id = {}
    for rec in tracing.recent():
        if rec["kind"] == "generation":
            by_id[rec["fields"].get("request_id", rec["trace_id"])] = rec
    # match traces to results by token count + evictions fields
    preempts = [e for rec in by_id.values()
                for e in rec.get("events", ())
                if e["name"] == "preempt"]
    replays = [e for rec in by_id.values()
               for e in rec.get("events", ())
               if e["name"] == "replay"]
    assert len(preempts) >= 1
    assert len(replays) >= 1
    assert replays[0]["evictions"] >= 1
    # every trace is complete and ordered, replay or not
    for rec in by_id.values():
        names = [s for s, _ in rec["stages"]]
        assert names[0] == "submit" and names[-1] == "done"
        offs = [t for _, t in rec["stages"]]
        assert offs == sorted(offs)
        # TTFT observed exactly once even across replay
        assert names.count("first_token") == 1


def test_generation_trace_decomposition_timers():
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest, init_params)
    cfg = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                        max_seq_len=32)
    params = init_params(cfg, seed=0)
    eng = GenerationEngine(cfg, params, num_blocks=64, block_size=4,
                           decode_width=4)
    t0 = timer_get("TIMER_generation_ttft_us")["count"]
    q0 = timer_get("TIMER_generation_queue_wait_us")["count"]
    eng.generate([GenerationRequest(prompt=[1, 2, 3],
                                    max_new_tokens=4)])
    assert timer_get("TIMER_generation_ttft_us")["count"] - t0 == 1
    assert timer_get("TIMER_generation_queue_wait_us")["count"] - q0 == 1
    rec = tracing.recent()[-1]
    assert rec["kind"] == "generation"
    assert rec["fields"]["finish_reason"] in ("eos", "length")
    assert rec["tokens"] == 4


# ---------------------------------------------------------------------------
# /tracez endpoint
# ---------------------------------------------------------------------------

def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_tracez_text_and_json():
    from paddle_tpu import introspect
    tr = tracing.begin("serving", deadline=1e-9)
    tr.stage("admit")
    tr.stage("execute")
    time.sleep(0.001)
    tr.finish()
    g = tracing.begin("generation")
    g.stage("prefill_start")
    g.token()
    g.token()
    g.finish(finish_reason="length")
    srv = introspect.start(port=0)
    try:
        code, text = _get(srv.url + "/tracez")
        assert code == 200
        assert "request traces" in text
        assert tr.trace_id in text
        assert "DEADLINE_MISSED" in text
        assert "rolling latency" in text
        code, body = _get(srv.url + "/tracez?format=json")
        assert code == 200
        payload = json.loads(body)
        assert payload["enabled"] is True
        ids = [r["trace_id"] for r in payload["recent"]]
        assert tr.trace_id in ids and g.trace_id in ids
        assert "generation_ttft" in payload["rolling_us"]
        # exemplars carry the full timeline
        ex = [r for r in payload["exemplars"]
              if r["trace_id"] == tr.trace_id]
        assert ex and ex[0]["deadline_missed"]
        # /statusz carries the rolling tracing summary
        code, body = _get(srv.url + "/statusz")
        st = json.loads(body)["tracing"]
        assert st["enabled"] is True
        assert st["completed"] >= 2
        # the index advertises /tracez
        code, body = _get(srv.url + "/")
        assert "/tracez" in body
    finally:
        introspect.stop()


# ---------------------------------------------------------------------------
# one-flag-lookup contract
# ---------------------------------------------------------------------------

def test_disabled_path_is_one_flag_lookup(monkeypatch):
    """begin() is the ONLY flag-lookup site: a pooled request threads
    the returned no-op trace everywhere, so disabling tracing costs
    exactly one dict lookup per request."""
    import paddle_tpu.tracing as tracing_mod
    set_flags({"FLAGS_request_tracing": False})
    calls = []
    real = tracing_mod.get_flag

    def counting(name, default=None):
        if name == "FLAGS_request_tracing":
            calls.append(name)
        return real(name, default)

    monkeypatch.setattr(tracing_mod, "get_flag", counting)
    tr = tracing_mod.begin("serving")
    assert tr is tracing.NOOP_TRACE
    tr.stage("admit")
    tr.token()
    tr.finish()
    assert len(calls) == 1
