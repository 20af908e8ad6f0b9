"""Failpoint-driven fault injection + self-healing pools (ISSUE 9,
docs/robustness.md).

Covers the tentpole: the spec grammar (triggers/actions, error cases),
the one-dict-lookup disarmed hot path (pinned the same way as
tracing's one-flag-lookup), hit-count bookkeeping that survives
disarm, the /failpointz HTTP surface (GET sites + POST arm/disarm),
env-var arming in a child process, and fault injection threaded
through the real stack: executor dispatch, the AOT program cache
(corrupt-on-load self-heal), the supervised PredictorPool /
GenerationPool (restart + backoff + readiness degradation + restart
budget exhaustion + typed PoolRestarted on in-flight futures),
deadline-burned-at-admit shedding, the bounded-blocking submit
timeout (satellite 2), the _reset_engine gauge retraction
(satellite 1), and preemption-replay determinism under an injected
decode fault (satellite 3).
"""
import json
import os
import random
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import failpoints, layers
from paddle_tpu.failpoints import InjectedFault
from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                   GenerationPool, GenerationRequest,
                                   SamplingParams, init_params)
from paddle_tpu.inference import Config
from paddle_tpu.monitor import gauge_get, gauge_set, stat_get, timer_get
from paddle_tpu.serving import (DeadlineBurned, PoolRestarted,
                                PredictorPool, ServingQueueFull)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm_all():
    failpoints.disarm()
    yield
    failpoints.disarm()


@pytest.fixture
def flag_guard():
    from paddle_tpu import flags as F
    saved = dict(F._values)
    yield
    F._values.clear()
    F._values.update(saved)


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


def _pool(model_dir, **kw):
    cfg = Config(model_dir)
    cfg.switch_shape_bucketing(True, buckets=[1, 2, 4, 8])
    return PredictorPool(cfg, **kw)


def _fires(site, n):
    fired = 0
    for _ in range(n):
        try:
            failpoints.failpoint(site)
        except InjectedFault:
            fired += 1
    return fired


# ---------------------------------------------------------------------------
# spec grammar
# ---------------------------------------------------------------------------

def test_arm_spec_multi_clause():
    armed = failpoints.arm_spec(
        "t.a=raise@once; t.b=delay(5) ;;t.c=corrupt(4)@every(2)")
    assert armed == ["t.a", "t.b", "t.c"]
    s = failpoints.sites()
    assert s["t.a"]["armed"] == "t.a=raise@once"
    assert s["t.b"]["armed"] == "t.b=delay(5)"
    assert s["t.c"]["armed"] == "t.c=corrupt(4)@every(2)"
    assert failpoints.arm_spec("") == []  # blank spec is a no-op


@pytest.mark.parametrize("bad", [
    "noequals",                # no site=action
    "=raise",                  # empty site
    "x=frobnicate",            # unknown action
    "x=raise@sometimes",       # unknown trigger
    "x=delay",                 # delay needs ms
    "x=raise@every",           # every needs N
    "x=raise@every(0)",        # N >= 1
    "x=raise@prob(0.5)",       # prob needs an explicit seed
    "x=raise@prob(1.5,3)",     # p out of range
    "x=raise@once(",           # malformed call syntax
])
def test_arm_spec_rejects_malformed(bad):
    with pytest.raises(ValueError):
        failpoints.arm_spec(bad)


def test_triggers():
    failpoints.arm_spec("t.always=raise")
    assert _fires("t.always", 5) == 5

    failpoints.arm_spec("t.every=raise@every(3)")
    assert [_fires("t.every", 1) for _ in range(9)] == \
        [0, 0, 1, 0, 0, 1, 0, 0, 1]

    failpoints.arm_spec("t.after=raise@after(2)")
    assert [_fires("t.after", 1) for _ in range(5)] == [0, 0, 1, 1, 1]

    # prob requires an explicit seed, so the fire count is reproducible
    failpoints.arm_spec("t.prob=raise@prob(0.5,42)")
    rng = random.Random(42)
    want = sum(rng.random() < 0.5 for _ in range(10))
    assert 0 < want < 10 and _fires("t.prob", 10) == want


def test_once_fires_once_then_auto_disarms():
    failpoints.arm_spec("t.once=raise@once")
    assert _fires("t.once", 5) == 1
    s = failpoints.sites()["t.once"]
    assert s["armed"] is None          # auto-disarmed
    # the 4 post-disarm calls took the zero-overhead path: not counted
    assert s["calls"] == 1 and s["fires"] == 1


def test_actions():
    failpoints.arm_spec("t.msg=raise(boom)")
    with pytest.raises(InjectedFault) as ei:
        failpoints.failpoint("t.msg")
    assert ei.value.site == "t.msg" and "boom" in str(ei.value)

    payload = object()
    failpoints.arm_spec("t.delay=delay(30)")
    t0 = time.monotonic()
    assert failpoints.failpoint("t.delay", payload) is payload
    assert time.monotonic() - t0 >= 0.025

    blob = bytes(range(64))
    failpoints.arm_spec("t.cor=corrupt(4)")
    out = failpoints.failpoint("t.cor", blob)
    assert len(out) == len(blob)
    assert sum(a != b for a, b in zip(out, blob)) == 4

    failpoints.arm_spec("t.trunc=raise")  # overwrite below re-arms
    failpoints.arm_spec("t.trunc=truncate(10)")
    assert failpoints.failpoint("t.trunc", b"x" * 100) == b"x" * 10
    failpoints.arm_spec("t.trunc=truncate")  # default: keep half
    assert failpoints.failpoint("t.trunc", b"x" * 100) == b"x" * 50

    # byte actions pass non-bytes payloads through untouched
    failpoints.arm_spec("t.passthru=corrupt")
    assert failpoints.failpoint("t.passthru", payload) is payload


def test_hit_counts_survive_disarm_until_reset():
    failpoints.arm_spec("t.counted=raise")
    assert _fires("t.counted", 3) == 3
    failpoints.disarm("t.counted")
    s = failpoints.sites()["t.counted"]
    assert s["armed"] is None and s["calls"] == 3 and s["fires"] == 3
    failpoints.reset_counts()
    # a private site with no counts and no arming disappears; the
    # declared sites are always listed
    assert "t.counted" not in failpoints.sites()
    assert set(failpoints.KNOWN_SITES) <= set(failpoints.sites())


def test_armed_context_manager_disarms_on_exit_and_error():
    with failpoints.armed("t.ctx=raise@once"):
        assert failpoints.sites()["t.ctx"]["armed"] is not None
    assert failpoints.sites().get("t.ctx", {}).get("armed") is None

    with pytest.raises(InjectedFault):
        with failpoints.armed("t.ctx=raise"):
            failpoints.failpoint("t.ctx")
    assert failpoints.sites()["t.ctx"]["armed"] is None


# ---------------------------------------------------------------------------
# the zero-overhead pin: disarmed == ONE dict lookup
# ---------------------------------------------------------------------------

def test_disarmed_hook_is_one_dict_lookup(monkeypatch):
    """Same contract (and same pin idiom) as tracing.begin: production
    code on the serving/executor hot path calls failpoint() inline, so
    the disarmed cost must stay a single _ARMED.get."""
    class CountingDict(dict):
        gets = 0

        def get(self, *a, **kw):
            CountingDict.gets += 1
            return dict.get(self, *a, **kw)

    monkeypatch.setattr(failpoints, "_ARMED", CountingDict())
    payload = object()
    assert failpoints.failpoint("serving.execute", payload) is payload
    assert CountingDict.gets == 1


def test_env_var_arms_at_import():
    code = ("import paddle_tpu.failpoints as fp\n"
            "print(fp.sites()['executor.dispatch']['armed'])\n")
    env = dict(os.environ,
               PADDLE_TPU_FAILPOINTS="executor.dispatch=raise@once",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "executor.dispatch=raise@once" in out.stdout


# ---------------------------------------------------------------------------
# /failpointz HTTP surface
# ---------------------------------------------------------------------------

def test_failpointz_endpoint():
    from paddle_tpu import introspect
    srv = introspect.start(port=0)
    try:
        fpz = json.load(urllib.request.urlopen(
            srv.url + "/failpointz", timeout=10))
        assert set(failpoints.KNOWN_SITES) <= set(fpz["sites"])

        # POST ?arm= with the spec grammar
        r = json.load(urllib.request.urlopen(
            srv.url + "/failpointz?arm=serving.execute=raise@once",
            data=b"", timeout=10))
        assert r["sites"]["serving.execute"]["armed"] == \
            "serving.execute=raise@once"
        with pytest.raises(InjectedFault):
            failpoints.failpoint("serving.execute")

        # armed sites surface on /statusz; POST ?disarm= clears
        urllib.request.urlopen(
            srv.url + "/failpointz?arm=serving.execute=delay(1)",
            data=b"", timeout=10)
        statusz = json.load(urllib.request.urlopen(
            srv.url + "/statusz", timeout=10))
        assert statusz["failpoints_armed"]["serving.execute"] == \
            "serving.execute=delay(1)"
        r = json.load(urllib.request.urlopen(
            srv.url + "/failpointz?disarm=serving.execute",
            data=b"", timeout=10))
        assert r["sites"]["serving.execute"]["armed"] is None

        # a raw body is also accepted as a spec
        r = json.load(urllib.request.urlopen(
            srv.url + "/failpointz", data=b"t.body=raise@once",
            timeout=10))
        assert r["sites"]["t.body"]["armed"] == "t.body=raise@once"
        failpoints.disarm("t.body")

        # counts survive the auto-disarm and are scrapeable
        fpz = json.load(urllib.request.urlopen(
            srv.url + "/failpointz", timeout=10))
        assert fpz["sites"]["serving.execute"]["fires"] >= 1

        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/failpointz?arm=bogus",
                                   data=b"", timeout=10)
        assert ei.value.code == 400
    finally:
        introspect.stop()


# ---------------------------------------------------------------------------
# injection through the real stack
# ---------------------------------------------------------------------------

def test_executor_dispatch_fault_then_recovery():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        y = layers.fc(x, 2, name="fp_exec")
    exe = pt.Executor()
    exe.run(startup)
    feed = {"x": np.ones((2, 4), np.float32)}
    base, = exe.run(main, feed=feed, fetch_list=[y])
    with failpoints.armed("executor.dispatch=raise@once"):
        with pytest.raises(InjectedFault):
            exe.run(main, feed=feed, fetch_list=[y])
        # the very next run succeeds, bitwise-identically
        again, = exe.run(main, feed=feed, fetch_list=[y])
    assert np.asarray(again).tobytes() == np.asarray(base).tobytes()
    assert failpoints.sites()["executor.dispatch"]["fires"] >= 1


def test_program_cache_corrupt_on_load_self_heals(tmp_path):
    """program_cache.load=corrupt flips bytes of the on-disk entry as
    it is read: the loader must detect the damage, count it, recompile
    bitwise-identically, and re-store a healthy entry."""
    cache = str(tmp_path / "aot")
    width = 41  # unique program so cache stats are attributable
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [width])
        h = layers.fc(x, 24, act="relu")
        loss = layers.mean(h)
        pt.optimizer.SGD(0.1).minimize(loss, startup_program=startup,
                                       program=main)
    feed = {"x": np.ones((4, width), np.float32)}

    def run():
        exe = pt.Executor(program_cache_dir=cache)
        scope = pt.Scope()
        exe.run(startup, scope=scope)
        return exe.run(main, feed=feed, fetch_list=[loss.name],
                       scope=scope, use_program_cache=True)

    cold = run()
    c0 = stat_get("STAT_program_cache_corrupt")
    with failpoints.armed("program_cache.load=corrupt"):
        healed = run()
    assert stat_get("STAT_program_cache_corrupt") > c0
    assert healed[0].tobytes() == cold[0].tobytes()
    # disarmed again: the re-stored entry serves a clean disk hit
    h0 = stat_get("STAT_program_cache_trace_hit")
    warm = run()
    assert stat_get("STAT_program_cache_trace_hit") > h0
    assert warm[0].tobytes() == cold[0].tobytes()


# ---------------------------------------------------------------------------
# supervised PredictorPool: restart, readiness, budget, shedding
# ---------------------------------------------------------------------------

def test_serving_pool_restarts_and_recovers(flag_guard, model_dir):
    pt.set_flags({"FLAGS_pool_restart_backoff_ms": 1.0,
                  "FLAGS_pool_max_restarts": 3})
    pool = _pool(model_dir, max_batch=4)
    try:
        x = np.ones((2, 6), np.float32)
        base = np.asarray(pool.run([x])[0])
        r0 = stat_get("STAT_serving_restarts")
        failpoints.arm_spec("serving.execute=raise")
        # two consecutive zero-success batches escalate to a worker
        # crash; each failed request resolves typed, never hangs
        for _ in range(2):
            with pytest.raises((InjectedFault, PoolRestarted)):
                pool.run([x], timeout=30.0)
        failpoints.disarm("serving.execute")
        out, deadline = None, time.monotonic() + 30.0
        while time.monotonic() < deadline:
            try:
                out = np.asarray(pool.run([x], timeout=2.0)[0])
                break
            except (PoolRestarted, InjectedFault, ServingQueueFull,
                    TimeoutError):
                time.sleep(0.05)
        assert out is not None and out.tobytes() == base.tobytes()
        assert stat_get("STAT_serving_restarts") > r0
        s = failpoints.sites()["serving.execute"]
        assert s["fires"] >= 2
    finally:
        pool.close()


def test_serving_pool_readiness_degrades_during_restart(flag_guard,
                                                        model_dir):
    from paddle_tpu import introspect
    # the supervisor reads the backoff flags at thread start -> set
    # them BEFORE the pool is created; a long backoff makes the
    # unready window observable
    pt.set_flags({"FLAGS_pool_restart_backoff_ms": 400.0,
                  "FLAGS_pool_max_restarts": 3})
    pool = _pool(model_dir, max_batch=4)
    name = "serving_pool_%d" % id(pool)
    try:
        pool.warmup([np.zeros((1, 6), np.float32)])
        assert introspect.readiness()[1][name] is True
        failpoints.arm_spec("serving.execute=raise")
        for _ in range(2):
            with pytest.raises((InjectedFault, PoolRestarted)):
                pool.run([np.ones((1, 6), np.float32)], timeout=30.0)
        failpoints.disarm("serving.execute")
        saw_unready, deadline = False, time.monotonic() + 5.0
        while time.monotonic() < deadline and not saw_unready:
            saw_unready = introspect.readiness()[1][name] is False
            time.sleep(0.01)
        assert saw_unready  # /readyz degraded during the backoff
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline \
                and not introspect.readiness()[1][name]:
            time.sleep(0.05)
        assert introspect.readiness()[1][name] is True  # healed
    finally:
        pool.close()


def test_serving_pool_restart_budget_exhausts_to_terminal(flag_guard,
                                                          model_dir):
    from paddle_tpu import introspect
    pt.set_flags({"FLAGS_pool_restart_backoff_ms": 1.0,
                  "FLAGS_pool_max_restarts": 1})
    pool = _pool(model_dir, max_batch=4)
    try:
        x = np.ones((1, 6), np.float32)
        e0 = stat_get("STAT_serving_restart_exhausted")
        failpoints.arm_spec("serving.execute=raise")
        terminal, deadline = None, time.monotonic() + 60.0
        while terminal is None and time.monotonic() < deadline:
            try:
                pool.run([x], timeout=5.0)
            except PoolRestarted as e:
                if pool._failed:
                    terminal = e
            except (InjectedFault, ServingQueueFull, TimeoutError):
                pass
            time.sleep(0.01)
        assert terminal is not None
        assert terminal.trace_id  # typed, attributable to a request
        assert stat_get("STAT_serving_restart_exhausted") == e0 + 1
        # terminal is sticky: reject at admit, stay unready
        with pytest.raises(PoolRestarted):
            pool.submit([x])
        assert introspect.readiness()[1]["serving_pool_%d"
                                         % id(pool)] is False
    finally:
        pool.close()


def test_serving_pool_concurrent_submitters_never_hang(flag_guard,
                                                       model_dir):
    pt.set_flags({"FLAGS_pool_restart_backoff_ms": 1.0,
                  "FLAGS_pool_max_restarts": 3})
    pool = _pool(model_dir, max_batch=8)
    try:
        failpoints.arm_spec("serving.execute=raise@every(2)")
        results = [None] * 8

        def worker(i):
            try:
                out = pool.run([np.ones((1, 6), np.float32)],
                               timeout=30.0)
                results[i] = ("ok", np.asarray(out[0]))
            except BaseException as e:  # noqa: BLE001 - recorded below
                results[i] = ("err", e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        failpoints.disarm("serving.execute")
        assert not any(t.is_alive() for t in threads)
        for res in results:
            assert res is not None  # every future resolved...
            tag, val = res
            if tag == "err":        # ...and errors are typed
                assert isinstance(val, (InjectedFault, PoolRestarted,
                                        ServingQueueFull, TimeoutError))
    finally:
        pool.close()


def test_serving_shed_at_admit_when_deadline_burned(model_dir):
    pool = _pool(model_dir, max_batch=4)
    try:
        s0 = stat_get("STAT_serving_shed_at_admit")
        with pytest.raises(DeadlineBurned) as ei:
            pool.submit([np.ones((1, 6), np.float32)], deadline=0.0)
        assert stat_get("STAT_serving_shed_at_admit") == s0 + 1
        assert ei.value.trace_id
    finally:
        pool.close()


def test_submit_timeout_bounds_queue_wait(model_dir):
    """Satellite 2: a full queue blocks submit for AT MOST `timeout`
    (sharing the request's deadline budget), then raises a
    ServingQueueFull that tells the caller when to retry."""
    pool = _pool(model_dir, max_batch=4, queue_depth=1, _start=False)
    try:
        x = np.ones((1, 6), np.float32)
        f1 = pool.submit([x])  # fills the only slot; no worker yet
        t0 = time.monotonic()
        with pytest.raises(ServingQueueFull) as ei:
            pool.submit([x], timeout=0.2)
        waited = time.monotonic() - t0
        assert 0.15 <= waited < 5.0
        assert ei.value.queue_depth == 1
        assert ei.value.retry_after_s > 0.0
        # the deadline is the SAME budget: it burns first when tighter
        s0 = stat_get("STAT_serving_shed_at_admit")
        with pytest.raises(DeadlineBurned):
            pool.submit([x], timeout=5.0, deadline=0.05)
        assert stat_get("STAT_serving_shed_at_admit") == s0 + 1
        # a worker that starts within the timeout drains the queue and
        # the blocked submit goes through (bounded blocking, not
        # fail-fast)
        threading.Timer(0.3, pool.start).start()
        f2 = pool.submit([x], timeout=30.0)
        np.asarray(f1.result(timeout=60.0)[0])
        np.asarray(f2.result(timeout=60.0)[0])
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# supervised GenerationPool
# ---------------------------------------------------------------------------

GCFG = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                     max_seq_len=32)


@pytest.fixture(scope="module")
def gparams():
    return init_params(GCFG, seed=0)


def _gengine(gparams, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("decode_width", 4)
    return GenerationEngine(GCFG, gparams, **kw)


def test_generation_pool_restarts_and_recovers(flag_guard, gparams):
    pt.set_flags({"FLAGS_pool_restart_backoff_ms": 1.0,
                  "FLAGS_pool_max_restarts": 3})
    pool = GenerationPool(_gengine(gparams))
    try:
        def req():
            return GenerationRequest(prompt=[1, 2, 3], max_new_tokens=4,
                                     sampling=SamplingParams(seed=0))
        base = pool.run(req(), timeout=120.0)
        r0 = stat_get("STAT_generation_restarts")
        failpoints.arm_spec("generation.decode=raise@once")
        with pytest.raises(PoolRestarted) as ei:
            pool.run(req(), timeout=120.0)
        assert ei.value.trace_id  # in-flight future got a typed error
        out, deadline = None, time.monotonic() + 30.0
        while time.monotonic() < deadline:
            try:
                out = pool.run(req(), timeout=10.0)
                break
            except (PoolRestarted, ServingQueueFull, TimeoutError):
                time.sleep(0.05)
        assert out is not None
        # deterministic sampler: the restarted engine reproduces the
        # pre-fault stream exactly
        assert out.tokens == base.tokens
        assert stat_get("STAT_generation_restarts") == r0 + 1
    finally:
        pool.close()


def test_generation_shed_at_admit_when_deadline_burned(gparams):
    pool = GenerationPool(_gengine(gparams), _start=False)
    try:
        s0 = stat_get("STAT_generation_shed_at_admit")
        with pytest.raises(DeadlineBurned) as ei:
            pool.submit(GenerationRequest(prompt=[1], max_new_tokens=2),
                        deadline=0.0)
        assert stat_get("STAT_generation_shed_at_admit") == s0 + 1
        assert ei.value.trace_id
    finally:
        pool.close()


def test_reset_engine_retracts_every_occupancy_gauge(gparams):
    """Satellite 1: a scrape BETWEEN a batch fault and the next request
    must see the true (empty) occupancy — _reset_engine retracts the
    gauges eagerly instead of waiting for the next allocation."""
    eng = _gengine(gparams, num_blocks=16)
    pool = GenerationPool(eng, _start=False)
    try:
        # simulate the occupancy a mid-batch fault leaves behind —
        # including shared blocks a (possibly poisoned) prefix cache
        # still references; the reset DROPS the cache
        blocks = eng.kv.alloc("seq", 3)
        if eng.prefix_cache is not None:
            eng.prefix_cache.insert("k", 8, blocks[:2])
            assert gauge_get("GAUGE_kv_shared_blocks") == 2
            assert gauge_get("GAUGE_generation_prefix_entries") == 1
        gauge_set("GAUGE_generation_active_seqs", 2)
        assert gauge_get("GAUGE_generation_blocks_used") == 3
        # poison the quant gauges too: the reset must re-derive them
        # from surviving engine state (fp32 here -> saved == 0)
        gauge_set("GAUGE_quant_weight_bytes_saved", 999)
        gauge_set("GAUGE_kv_bytes_per_seq", -1)
        gauge_set("GAUGE_kv_capacity_seqs", -1)
        pool._reset_engine()
        assert gauge_get("GAUGE_generation_blocks_free") == \
            eng.kv.num_blocks - 1
        assert gauge_get("GAUGE_generation_blocks_used") == 0
        assert gauge_get("GAUGE_generation_active_seqs") == 0
        assert gauge_get("GAUGE_kv_shared_blocks") == 0
        assert gauge_get("GAUGE_kv_blocks_saved") == 0
        assert gauge_get("GAUGE_generation_prefix_entries") == 0
        assert gauge_get("GAUGE_generation_prefix_blocks") == 0
        assert gauge_get("GAUGE_quant_weight_bytes_saved") == 0
        assert gauge_get("GAUGE_kv_bytes_per_seq") == \
            eng.kv_bytes_per_seq()
        assert gauge_get("GAUGE_kv_capacity_seqs") == \
            eng.kv_capacity_seqs()
    finally:
        pool.close()


def test_preemption_replay_under_injected_decode_fault(gparams):
    """Satellite 3: block-pool contention forces preemption+replay
    WHILE generation.decode faults are firing; the caller re-steps
    through the faults and every token stream must still match an
    uncontended, fault-free run. TTFT is recorded once per request,
    not re-recorded on replay."""
    def reqs():
        return [GenerationRequest(request_id=i, prompt=[i + 1] * 10,
                                  max_new_tokens=14,
                                  sampling=SamplingParams(
                                      temperature=0.9, seed=i))
                for i in range(3)]

    relaxed = _gengine(gparams)  # 64 blocks: no eviction pressure
    want = {r.request_id: r.tokens for r in relaxed.generate(reqs())}

    # 10 blocks (9 usable): 3 sequences of 6 blocks each cannot coexist
    eng = _gengine(gparams, num_blocks=10)
    for r in reqs():
        eng.submit(r)
    ev0 = stat_get("STAT_generation_evictions")
    t0 = timer_get("TIMER_generation_ttft_us")["count"]
    failpoints.arm_spec("generation.decode=raise@every(5)")
    faults, out, steps = 0, [], 0
    while not eng.idle and steps < 4000:
        steps += 1
        try:
            out.extend(eng.step())
        except InjectedFault:
            faults += 1  # re-step: the batch resumes where it was
    failpoints.disarm("generation.decode")
    assert eng.idle and faults > 0
    assert stat_get("STAT_generation_evictions") > ev0
    got = {r.request_id: r.tokens for r in out}
    assert got == want
    assert timer_get("TIMER_generation_ttft_us")["count"] == t0 + 3


def test_prefill_chunk_fault_resumes_with_no_duplication(gparams):
    """PR-10 satellite: a generation.prefill_chunk fault fires BETWEEN
    chunks of a mid-flight prompt, before the step mutates anything —
    re-stepping resumes the prompt stream exactly where it stopped.
    Stream equality with a fault-free run proves no prompt token was
    scattered twice (a duplicated write would corrupt the KV pool and
    diverge the logits)."""
    def req():
        return GenerationRequest(prompt=[3, 1, 4, 1, 5, 9, 2, 6, 5, 3],
                                 max_new_tokens=6,
                                 sampling=SamplingParams(temperature=0.8,
                                                         seed=2),
                                 request_id="A")

    base = _gengine(gparams, prefill_chunk=4).generate([req()])[0]
    eng = _gengine(gparams, prefill_chunk=4)  # 10-token prompt: 3 chunks
    eng.submit(req())
    failpoints.arm_spec("generation.prefill_chunk=raise@every(2)")
    faults, out, steps = 0, [], 0
    try:
        while not eng.idle and steps < 500:
            steps += 1
            try:
                out.extend(eng.step())
            except InjectedFault:
                faults += 1  # re-step resumes the same chunk
    finally:
        failpoints.disarm("generation.prefill_chunk")
    assert eng.idle and faults >= 1  # fired at a chunk boundary
    assert out[0].tokens == base.tokens


def test_generation_pool_recovers_mid_prompt_chunk_fault(flag_guard,
                                                         gparams):
    """PR-10 satellite: a fault injected mid-prompt (between prefill
    chunks) crashes the worker; the PR-9 supervisor restarts the pool
    and a resubmitted request regenerates the identical stream — no
    token duplicated, none lost."""
    pt.set_flags({"FLAGS_pool_restart_backoff_ms": 1.0,
                  "FLAGS_pool_max_restarts": 3})
    pool = GenerationPool(_gengine(gparams, prefill_chunk=4))
    try:
        def req():
            return GenerationRequest(prompt=[2] * 11, max_new_tokens=5,
                                     sampling=SamplingParams(seed=1))
        base = pool.run(req(), timeout=120.0)
        r0 = stat_get("STAT_generation_restarts")
        failpoints.arm_spec("generation.prefill_chunk=raise@once")
        with pytest.raises(PoolRestarted) as ei:
            pool.run(req(), timeout=120.0)
        assert ei.value.trace_id
        out, deadline = None, time.monotonic() + 30.0
        while time.monotonic() < deadline:
            try:
                out = pool.run(req(), timeout=10.0)
                break
            except (PoolRestarted, ServingQueueFull, TimeoutError):
                time.sleep(0.05)
        assert out is not None
        assert out.tokens == base.tokens
        assert stat_get("STAT_generation_restarts") == r0 + 1
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# quantized-KV failpoint (ISSUE 15 satellite)
# ---------------------------------------------------------------------------

def test_kv_quant_fault_aborts_cleanly_and_resumes(gparams):
    """generation.kv_quant fires before the mixed step's compiled call
    quantizes this step's K/V rows — and before ANY state mutation, so
    a caught fault re-steps to the identical stream. The site only
    exists on the quantized path: an fp32 engine never calls it."""
    assert "generation.kv_quant" in failpoints.KNOWN_SITES

    def reqs():
        return [GenerationRequest(request_id=i, prompt=[i + 1] * 6,
                                  max_new_tokens=6,
                                  sampling=SamplingParams(seed=i))
                for i in range(2)]

    clean = _gengine(gparams, prefill_chunk=4, quant_mode="int8")
    want = {r.request_id: r.tokens for r in clean.generate(reqs())}

    eng = _gengine(gparams, prefill_chunk=4, quant_mode="int8")
    for r in reqs():
        eng.submit(r)
    failpoints.arm_spec("generation.kv_quant=raise@every(3)")
    faults, out, steps = 0, [], 0
    try:
        while not eng.idle and steps < 1000:
            steps += 1
            try:
                out.extend(eng.step())
            except InjectedFault:
                faults += 1  # re-step: nothing was mutated
    finally:
        failpoints.disarm("generation.kv_quant")
    assert eng.idle and faults > 0
    assert {r.request_id: r.tokens for r in out} == want
    # the quantize path really ran (blocks counted through it)
    assert stat_get("STAT_generation_kv_quant_blocks") > 0

    # fp32 engines never reach the site: armed 'raise' cannot fire
    fp32 = _gengine(gparams, prefill_chunk=4)
    failpoints.arm_spec("generation.kv_quant=raise")
    try:
        res = fp32.generate(reqs())
    finally:
        failpoints.disarm("generation.kv_quant")
    assert {r.request_id: r.tokens for r in res} == want


def test_reset_engine_retracts_quant_gauges_for_quantized_engine(
        gparams):
    """A QUANTIZED engine rebuilt by the supervisor must republish its
    true quant gauges (nonzero saved bytes, quantized kv_bytes_per_seq)
    — retraction means re-derivation, not zeroing."""
    eng = _gengine(gparams, prefill_chunk=4, quant_mode="int8",
                   num_blocks=16)
    pool = GenerationPool(eng, _start=False)
    try:
        saved = gauge_get("GAUGE_quant_weight_bytes_saved")
        per_seq = gauge_get("GAUGE_kv_bytes_per_seq")
        assert saved > 0
        gauge_set("GAUGE_quant_weight_bytes_saved", 1)
        gauge_set("GAUGE_kv_bytes_per_seq", 1)
        pool._reset_engine()
        assert gauge_get("GAUGE_quant_weight_bytes_saved") == saved
        assert gauge_get("GAUGE_kv_bytes_per_seq") == per_seq
        assert per_seq == eng.kv_bytes_per_seq()
    finally:
        pool.close()
