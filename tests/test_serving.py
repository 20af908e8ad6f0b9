"""Serving-grade Predictor tests (ISSUE 4, docs/serving.md).

Covers the tentpole: the bucket ladder parser, shape-bucketed
Predictor execution (row parity with exact shapes + pinned
STAT_executor_compile deltas), compile-ahead warmup through the AOT
program cache (zero steady-state recompiles), the PredictorPool
micro-batcher (multi-threaded mixed-shape stress with row parity
vs serial execution, serving counter deltas, queue backpressure,
error isolation, lifecycle), and the framework-free SerializedCore
batch padding (static pad-up + overflow, env-ladder for
dynamic-batch exports).
"""
import os
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, serving
from paddle_tpu.inference import (Config, bucket_for, create_predictor,
                                  parse_bucket_ladder)
from paddle_tpu.monitor import stat_get


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


def _assert_rows_close(got, want):
    """A request's rows, whatever batch they rode in. Not bit for bit:
    XLA:CPU chooses its matmul tiling from the batch's shape, so a
    row's last bits move with the bucket (measured over 40 seeds x 10
    sizes: a padded bucket and the exact batch read at most 2.4e-7
    apart on outputs up to 1.6, a tenth of this budget; the absolute
    part is for outputs near zero). A row taken from the wrong request
    differs in its first digit (4.8e-2 at the least)."""
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _reqs(sizes, width=6, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randn(int(b), width).astype(np.float32) for b in sizes]


# ---------------------------------------------------------------------------
# ladder parsing / bucket selection
# ---------------------------------------------------------------------------

def test_parse_bucket_ladder():
    assert parse_bucket_ladder("pow2:16") == [1, 2, 4, 8, 16]
    assert parse_bucket_ladder("8, 1,4,4") == [1, 4, 8]
    assert parse_bucket_ladder([3, 1, 3]) == [1, 3]
    assert parse_bucket_ladder("") == []
    assert parse_bucket_ladder(None) == []


def test_bucket_for():
    ladder = [1, 2, 4, 8]
    assert bucket_for(1, ladder) == 1
    assert bucket_for(3, ladder) == 4
    assert bucket_for(8, ladder) == 8
    assert bucket_for(9, ladder) is None  # overflow -> exact shape
    assert bucket_for(1, []) is None


def test_bad_bucket_config(model_dir):
    cfg = Config(model_dir)
    with pytest.raises(ValueError):
        cfg.switch_shape_bucketing(True, axes=(1,))  # must include 0


# ---------------------------------------------------------------------------
# bucketed Predictor
# ---------------------------------------------------------------------------

def test_bucketed_parity_and_compile_count(model_dir):
    sizes = [1, 3, 5, 6, 7, 2, 3, 5]  # 6 distinct -> 4 buckets
    reqs = _reqs(sizes)

    plain = create_predictor(Config(model_dir))
    expected = [np.asarray(plain.run([r])[0]) for r in reqs]

    cfg = Config(model_dir)
    cfg.switch_shape_bucketing(True, buckets=[1, 2, 4, 8])
    bucketed = create_predictor(cfg)
    c0 = stat_get("STAT_executor_compile")
    h0 = stat_get("STAT_predictor_bucket_hit")
    outs = [np.asarray(bucketed.run([r])[0]) for r in reqs]
    compiles = stat_get("STAT_executor_compile") - c0

    for o, e in zip(outs, expected):
        assert o.shape == e.shape
        _assert_rows_close(o, e)
    # 8 requests, 6 distinct sizes, but only buckets {1,2,4,8} compile
    assert compiles == 4
    assert stat_get("STAT_predictor_bucket_hit") - h0 == 4


def test_bucket_overflow_runs_exact(model_dir):
    cfg = Config(model_dir)
    cfg.switch_shape_bucketing(True, buckets=[1, 2, 4])
    p = create_predictor(cfg)
    o0 = stat_get("STAT_predictor_bucket_overflow")
    (r,) = _reqs([9])
    out = np.asarray(p.run([r])[0])
    assert out.shape[0] == 9
    assert stat_get("STAT_predictor_bucket_overflow") - o0 == 1


def test_warmup_kills_steady_state_recompiles(model_dir, tmp_path):
    cfg = Config(model_dir)
    cfg.switch_shape_bucketing(True, buckets="pow2:8")
    cfg.enable_program_cache(str(tmp_path / "aot"))
    p = create_predictor(cfg)
    report = p.warmup_buckets([np.zeros((1, 6), np.float32)])
    assert sorted(report) == [1, 2, 4, 8]
    assert all("error" not in v for v in report.values())

    c0 = stat_get("STAT_executor_compile")
    for r in _reqs([1, 2, 3, 5, 8, 4, 7]):
        p.run([r])
    assert stat_get("STAT_executor_compile") - c0 == 0


def test_warmup_requires_bucketing(model_dir):
    p = create_predictor(Config(model_dir))
    with pytest.raises(RuntimeError):
        p.warmup_buckets([np.zeros((1, 6), np.float32)])


# ---------------------------------------------------------------------------
# PredictorPool
# ---------------------------------------------------------------------------

def test_pool_concurrent_parity_and_counters(model_dir):
    sizes = np.random.RandomState(3).randint(1, 9, size=48)
    reqs = _reqs(sizes, seed=1)
    ref = create_predictor(Config(model_dir))
    expected = [np.asarray(ref.run([r])[0]) for r in reqs]

    cfg = Config(model_dir)
    cfg.switch_shape_bucketing(True, buckets="pow2:32")
    with serving.PredictorPool(cfg, max_batch=32,
                               batch_timeout_ms=5.0) as pool:
        pool.warmup([np.zeros((1, 6), np.float32)])
        q0 = stat_get("STAT_serving_requests")
        b0 = stat_get("STAT_serving_batches")
        rw0 = stat_get("STAT_serving_batched_rows")
        c0 = stat_get("STAT_executor_compile")

        outs = [None] * len(reqs)

        def worker(tid):
            for i in range(tid, len(reqs), 8):
                outs[i] = np.asarray(pool.run([reqs[i]])[0])

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        for o, e in zip(outs, expected):
            _assert_rows_close(o, e)             # vs serial
        assert stat_get("STAT_executor_compile") - c0 == 0
        assert stat_get("STAT_serving_requests") - q0 == len(reqs)
        batches = stat_get("STAT_serving_batches") - b0
        assert 1 <= batches < len(reqs)  # actually coalesced
        assert stat_get("STAT_serving_batched_rows") - rw0 == \
            sum(int(s) for s in sizes)


def test_pool_backpressure(model_dir):
    cfg = Config(model_dir)
    pred = create_predictor(cfg)
    pool = serving.PredictorPool(pred, queue_depth=2, bucketing=False,
                                 _start=False)  # batcher never drains
    (r,) = _reqs([2])
    f1, f2 = pool.submit([r]), pool.submit([r])
    rej0 = stat_get("STAT_serving_rejected")
    with pytest.raises(serving.ServingQueueFull):
        pool.submit([r], timeout=0.05)
    assert stat_get("STAT_serving_rejected") - rej0 == 1
    pool.close()
    # queued-but-never-run requests fail loudly, not silently hang
    with pytest.raises(RuntimeError):
        f1.result(timeout=1.0)
    with pytest.raises(RuntimeError):
        f2.result(timeout=1.0)
    with pytest.raises(RuntimeError):
        pool.submit([r])  # closed pool rejects new work


def test_pool_error_isolation(model_dir):
    cfg = Config(model_dir)
    cfg.switch_shape_bucketing(True, buckets="pow2:8")
    with serving.PredictorPool(cfg, batch_timeout_ms=1.0) as pool:
        (good,) = _reqs([2])
        expected = np.asarray(pool.run([good])[0])
        with pytest.raises(Exception):
            pool.run([np.zeros((2, 5), np.float32)])  # wrong width
        # the pool survives a poisoned request
        np.testing.assert_array_equal(
            np.asarray(pool.run([good])[0]), expected)


def test_pool_batch_retry_preserves_order_and_identity(model_dir):
    """Regression for the _execute ORDER/IDENTITY CONTRACT: when a
    coalesced batch raises, the retry walks the batch in FIFO-pop
    order and binds each retry's outputs to ITS OWN request's future —
    a concurrent submitter never receives a batch-mate's rows, and no
    request is dropped or reordered by the fault."""
    inner = create_predictor(Config(model_dir))

    class FaultOnce:
        """Predictor proxy: the first multi-row (coalesced) execution
        raises; every run is logged so the retry order is observable."""

        def __init__(self, p):
            self._p = p
            self.calls = []
            self.retry_order = []
            self.faulted = False

        @property
        def feed_names(self):
            return self._p.feed_names

        def run(self, feeds):
            self.calls.append(int(feeds[0].shape[0]))
            if not self.faulted and feeds[0].shape[0] > 1:
                self.faulted = True
                raise RuntimeError("injected batch fault")
            if self.faulted and feeds[0].shape[0] == 1:
                self.retry_order.append(float(feeds[0][0, 0]))
            return self._p.run(feeds)

    proxy = FaultOnce(inner)
    pool = serving.PredictorPool(proxy, max_batch=32, bucketing=False,
                                 batch_timeout_ms=50.0, _start=False)
    n = 6
    # each submitter's feed encodes its identity in the row values
    reqs = [np.full((1, 6), float(i), np.float32) for i in range(n)]
    futs = [None] * n

    def worker(i):
        futs[i] = pool.submit([reqs[i]])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # the FIFO order the batcher will pop (whatever the thread race
    # produced) — read it before the batcher starts
    fifo = [float(r.feeds[0][0, 0]) for r in pool._queue]
    assert sorted(fifo) == [float(i) for i in range(n)]
    pool.start()
    try:
        for i in range(n):
            out = np.asarray(futs[i].result(timeout=60.0)[0])
            expected = np.asarray(inner.run([reqs[i]])[0])
            # identity: submitter i's future carries the outputs of
            # submitter i's feeds, bit for bit
            np.testing.assert_array_equal(out, expected)
    finally:
        pool.close()
    # one faulted coalesced run, then per-request retries in FIFO order
    assert proxy.calls[0] == n
    assert proxy.calls[1:n + 1] == [1] * n
    assert proxy.retry_order == fifo


def test_pool_rejects_mismatched_feeds(model_dir):
    with serving.PredictorPool(Config(model_dir)) as pool:
        with pytest.raises(ValueError):
            pool.submit([])  # wrong feed count
        with pytest.raises(ValueError):
            pool.submit([np.zeros((0, 6), np.float32)])  # empty batch


# ---------------------------------------------------------------------------
# SerializedCore padding (framework-free path)
# ---------------------------------------------------------------------------

def _export(model_dir, tmp_path, batch, **kw):
    p = create_predictor(Config(model_dir))
    d = str(tmp_path / ("artifact_b%d" % batch))
    p.export_serialized(d, [np.zeros((batch, 6), np.float32)], **kw)
    return d


def test_serialized_static_pad_up(model_dir, tmp_path):
    from paddle_tpu.serving_core import SerializedCore
    d = _export(model_dir, tmp_path, batch=8)
    core = SerializedCore(d)
    ref = create_predictor(Config(model_dir))
    (r,) = _reqs([3])
    out = core.run([r])[0]
    assert out.shape[0] == 3
    _assert_rows_close(out, np.asarray(ref.run([r])[0]))
    assert core.stats["padded_calls"] == 1
    assert core.stats["pad_rows"] == 5
    with pytest.raises(ValueError):  # b > compiled batch is loud
        core.run([np.zeros((9, 6), np.float32)])


def test_serialized_bucket_env_disable(model_dir, tmp_path, monkeypatch):
    from paddle_tpu.serving_core import _bucket_ladder
    monkeypatch.setenv("PADDLE_TPU_SHAPE_BUCKETS", "")
    assert _bucket_ladder() == []
    monkeypatch.setenv("PADDLE_TPU_SHAPE_BUCKETS", "1,2,4")
    assert _bucket_ladder() == [1, 2, 4]
    monkeypatch.delenv("PADDLE_TPU_SHAPE_BUCKETS")
    assert _bucket_ladder() == [2 ** i for i in range(8)]


# ---------------------------------------------------------------------------
# run() timeout budget (regression: timeout was double-spent)
# ---------------------------------------------------------------------------

def test_run_timeout_is_one_shared_budget(model_dir):
    """run(timeout=T) used to hand T to submit() AND result(), so a
    request that spent 0.4s blocked on a full queue still got the full
    T to wait for a result — a 1s budget could block ~1.4s. With the
    serve loop stalled (never started), total wall time must stay ~T."""
    import time
    pool = serving.PredictorPool(Config(model_dir), queue_depth=1,
                                 _start=False)
    try:
        pool.submit(_reqs([1]))  # fill the queue: next submit blocks

        def free_slot_later():
            time.sleep(0.4)
            with pool._lock:
                pool._queue.popleft()
                pool._not_full.notify_all()

        t = threading.Thread(target=free_slot_later)
        t.start()
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            pool.run(_reqs([1]), timeout=1.0)
        elapsed = time.monotonic() - t0
        t.join()
        # submit consumed ~0.4s of the budget; result() must only get
        # the remainder. The double-spend bug made this ~1.4s.
        assert 0.85 <= elapsed <= 1.3, elapsed
    finally:
        pool.close()


def test_future_timeout_reports_elapsed_and_stage(model_dir):
    """A timed-out result() says how long it actually waited and the
    last lifecycle stage the request reached — and t_submit is on the
    monotonic clock (it was perf_counter, a different epoch than every
    deadline computation)."""
    import time
    pool = serving.PredictorPool(Config(model_dir), _start=False)
    try:
        fut = pool.submit(_reqs([2]))
        assert abs(fut.t_submit - time.monotonic()) < 5.0
        with pytest.raises(TimeoutError) as ei:
            fut.result(timeout=0.05)
        msg = str(ei.value)
        assert "elapsed" in msg
        assert "last completed stage: admit" in msg
    finally:
        pool.close()


def test_generation_run_timeout_is_one_shared_budget():
    """GenerationPool.run had the identical double-spend; same stalled
    serve-loop setup through the generation front door."""
    import time
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest, init_params)
    from paddle_tpu.generation.scheduler import GenerationPool
    cfg = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                        max_seq_len=32)
    eng = GenerationEngine(cfg, init_params(cfg, seed=0), num_blocks=16,
                           block_size=4, decode_width=2)
    pool = GenerationPool(eng, queue_depth=1, _start=False)
    try:
        pool.submit(GenerationRequest(prompt=[1, 2], max_new_tokens=2))

        def free_slot_later():
            time.sleep(0.4)
            with pool._lock:
                pool._queue.popleft()
                pool._not_full.notify_all()

        t = threading.Thread(target=free_slot_later)
        t.start()
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            pool.run(GenerationRequest(prompt=[3, 4], max_new_tokens=2),
                     timeout=1.0)
        elapsed = time.monotonic() - t0
        t.join()
        assert 0.85 <= elapsed <= 1.3, elapsed
    finally:
        pool.close()
