"""Benchmark: BERT-base pretrain + ResNet-50 train throughput on the
local chip (BASELINE.json metric: images/sec/chip (ResNet-50) +
tokens/sec/chip (BERT-base)).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
vs_baseline is min(bert_mfu, resnet_mfu) / 0.45 — the north star is
>=45% MFU on BOTH headline configs, so the conservative (worst) config
gates the score. Extra keys carry the per-config numbers and the proof
that the Pallas flash kernel is actually inside the compiled step
(round 2 silently benchmarked the fallback; never again).

FLOPs accounting (honest-MFU):
- BERT: analytic transformer FLOPs — 6*N_dense per token for the dense
  blocks (embedding-table rows excluded: a lookup is a gather, not a
  matmul), + 12*L*H*S per token for the attention score/value matmuls,
  + MLM head on the M masked positions only (6*H*V + 6*H*H per masked
  token) + pooler/NSP. The 6N-all-params model the round-2 bench used
  inflated MFU by counting ~23M embedding rows as matmul FLOPs.
- ResNet-50: ~4.09 GMACs/image at 224x224 => 2*MACs = 8.18 GFLOPs
  forward; fwd+bwd = 3x forward.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

# Peak dense bf16 matmul rate of one chip, by jax `device_kind`. A device
# that is not here is an error, not a default. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
_PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _peak_flops():
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in _PEAK_BF16_FLOPS:
        raise KeyError("no peak FLOP/s on record for device_kind %r; add "
                       "it to _PEAK_BF16_FLOPS with its source" % kind)
    return _PEAK_BF16_FLOPS[kind]


def _xla_flops(lowered):
    """FLOPs per step as XLA counts them, from lowered.cost_analysis()
    (a dict in the installed jax; no backend compile)."""
    v = lowered.cost_analysis().get("flops")
    return float(v) if v is not None else None


def _bench_bert():
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretraining_loss)
    from paddle_tpu.jit import TrainStep

    cfg = BertConfig()  # BERT-base, real training config (dropout on)
    B = int(os.environ.get("BENCH_BERT_B", "32"))
    S, M, steps = 512, 80, 30

    model = BertForPretraining(cfg)
    opt = pt.optimizer.Adam(1e-4, parameters=model.parameters())
    step = TrainStep(model, pretraining_loss, opt, amp_dtype="bfloat16")

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    # masked-position pretraining batch: M masked slots per row; labels
    # are the original ids at those positions (gathered — matches the
    # model's masked_positions contract, models/bert.py:176)
    pos = np.stack([rng.choice(S, M, replace=False) for _ in range(B)]
                   ).astype(np.int32)
    mlm = np.take_along_axis(ids, pos, axis=1).astype(np.int32)
    nsp = rng.randint(0, 2, (B, 1)).astype(np.int32)
    # device-resident synthetic batch: the bench measures the training
    # step; input staging overlap is the DataLoader prefetcher's job
    # (reader.py _DevicePrefetcher)
    ids, pos, mlm, nsp = (jax.device_put(x) for x in (ids, pos, mlm, nsp))
    inputs = (ids, None, None, pos)
    labels = (mlm, nsp)

    from paddle_tpu.nn import transformer as _tr
    _tr.reset_attention_path_log()
    # warm-up: the first call compiles (TrainStep pre-builds the
    # optimizer accumulators, so there is one compile), the second
    # proves the step is cached
    for _ in range(2):
        loss = step(inputs, labels)
        float(loss)

    # honest attention-path report: the router LOGS the path it took at
    # trace time (round-2 postmortem: never assume), and the bench
    # cross-checks against the router's own predicate — a mismatch means
    # the kernel dropped out of the step, and nothing measured after that
    # is the configuration this bench claims to measure
    paths = set(_tr.attention_paths_taken())
    attention_path = "flash" if paths == {"flash"} else \
        ("composed(xla)" if paths == {"composed"} else
         "mixed:%s" % sorted(paths))
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    # the bench config trains with dropout on, so the dropout-active
    # crossover governs the router's prediction
    if (_tr.routes_to_flash(S, head_dim, dropout_active=True)
            and attention_path != "flash"):
        raise RuntimeError(
            "router predicts flash at S=%d d=%d but the traced path was "
            "%s" % (S, head_dim, attention_path))
    import jax.numpy as jnp
    lowered = step._step_fn.lower(
        step._state, step._opt_state, step._lr_step,
        jax.random.PRNGKey(0),
        (tuple(jnp.asarray(x) if x is not None else None
               for x in inputs),
         tuple(jnp.asarray(x) for x in labels)))
    mosaic_in_hlo = "tpu_custom_call" in lowered.as_text()
    if attention_path == "flash" and not mosaic_in_hlo:
        raise RuntimeError("the router logged flash but the lowered step "
                           "holds no tpu_custom_call")
    # XLA's own per-step FLOP count (lowered.cost_analysis — no
    # backend compile) alongside the analytic hand-count below:
    # the r3 honest-MFU re-denomination never has to happen again
    # because both numbers now ship in every artifact
    xla_flops = _xla_flops(lowered)

    t0 = time.time()
    for _ in range(steps):
        loss = step(inputs, labels)
    float(loss)  # sync
    dt = (time.time() - t0) / steps
    tokens_per_sec = B * S / dt

    H, L, V = cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size
    I = cfg.intermediate_size
    # dense params per layer: qkv+out 4H^2 + ffn 2HI; 6 flops/param/token
    n_dense = L * (4 * H * H + 2 * H * I)
    flops_token = 6 * n_dense + 12 * L * H * S
    # heads: MLM transform H^2 + tied decoder H*V on M positions;
    # pooler H^2 + nsp 2H on 1 position — amortized over B*S tokens
    head = 6 * (H * H + H * V) * M + 6 * (H * H + 2 * H)
    flops_step = flops_token * B * S + head * B
    mfu = (flops_step / dt) / _peak_flops()
    return (tokens_per_sec, mfu, attention_path, mosaic_in_hlo, B,
            flops_step, xla_flops)


def _bench_resnet():
    import paddle_tpu as pt
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nn import functional as F

    import jax
    model = resnet50(num_classes=1000)
    B, HW, steps, flops_img = 256, 224, 20, 3 * 2 * 4.09e9

    opt = pt.optimizer.Momentum(0.1, 0.9, parameters=model.parameters())

    def loss_fn(logits, label):
        return F.cross_entropy(logits, label, reduction="mean")

    step = TrainStep(model, loss_fn, opt, amp_dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = jax.device_put(rng.randn(B, 3, HW, HW).astype(np.float32))
    y = jax.device_put(rng.randint(0, 1000, (B, 1)).astype(np.int64))

    for _ in range(2):
        loss = step((x,), (y,))
        float(loss)
    xla_flops = _xla_flops(step._step_fn.lower(
        step._state, step._opt_state, step._lr_step,
        jax.random.PRNGKey(0),
        ((jax.numpy.asarray(x),), (jax.numpy.asarray(y),))))
    t0 = time.time()
    for _ in range(steps):
        loss = step((x,), (y,))
    float(loss)
    dt = (time.time() - t0) / steps
    imgs_per_sec = B / dt
    mfu = (imgs_per_sec * flops_img) / _peak_flops()
    return imgs_per_sec, mfu, flops_img * B, xla_flops


def _compile_worker(cache_dir):
    """One cold/warm probe process for the `compile` block: run the
    12-layer BERT-shaped static train step (the
    tools/check_backward_replay.py program) through Executor.run with
    the persistent AOT cache at `cache_dir`, and report wall time to
    first results + the program-cache counters + a fetch digest. The
    parent runs this twice against one cache dir: the delta IS the
    retrace+recompile cold start the cache kills."""
    import hashlib
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import check_backward_replay as cbr
    import paddle_tpu as pt
    pt.set_flags({"FLAGS_program_cache_dir": cache_dir})

    shape = dict(layers_n=12, H=768, FF=3072, HEADS=12, S=128, B=8)
    for k in shape:  # shrinkable for quick CI probes of the same path
        env = os.environ.get("PT_COMPILE_BENCH_" + k.upper())
        if env:
            shape[k] = int(env)
    t0 = time.time()
    main, startup, loss, feed = cbr.build_bert_shaped(**shape)
    t_build = time.time() - t0
    exe = pt.Executor()
    t0 = time.time()
    exe.run(startup)
    t_startup = time.time() - t0
    t0 = time.time()
    outs = exe.run(main, feed=feed, fetch_list=[loss.name])
    t_first = time.time() - t0
    from paddle_tpu.monitor import get_float_stats
    st = get_float_stats()
    digest = hashlib.sha256(
        b"".join(np.ascontiguousarray(o).tobytes() for o in outs)
    ).hexdigest()
    t0 = time.time()  # steady-state step: the compute floor both the
    exe.run(main, feed=feed, fetch_list=[loss.name])  # cold and warm
    t_steady = time.time() - t0                       # first runs share
    print(json.dumps({
        "build_s": round(t_build, 3), "startup_s": round(t_startup, 3),
        "first_results_s": round(t_first, 3),
        "steady_s": round(t_steady, 3),
        "trace_hit": st.get("STAT_program_cache_trace_hit", 0),
        "trace_miss": st.get("STAT_program_cache_trace_miss", 0),
        "fetch_sha256": digest,
        "program": "bert%(layers_n)dL-H%(H)d-S%(S)d-B%(B)d" % shape}))


def _kill_group(proc):
    """SIGKILL a timed-out child's process group and reap it. Every
    child of this file is pinned to the CPU, so no chip is left held."""
    import signal
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        return proc.communicate(timeout=15)
    except subprocess.TimeoutExpired:
        return "", ""  # abandon the pipes rather than hang


def _spawn_compile(cache_dir, timeout=900):
    # the parent holds the chip, and a chip belongs to one process: the
    # child is pinned to the CPU through its environment, before it
    # imports jax
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--compile-worker",
         cache_dir],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        out, err = _kill_group(proc)
    for line in reversed((out or "").splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    sys.stderr.write(err or "")
    return None


def bench_compile():
    """cold_compile_s / warm_compile_s block: two subprocesses share a
    fresh AOT cache dir; the first pays trace+XLA compile, the second
    must hit the StableHLO trace cache AND the persistent XLA cache.
    CPU numbers are real (compile happens on the host) so this block is
    emitted off-TPU too, and the bench trajectory tracks the win from
    this round on."""
    import shutil
    import tempfile
    d = tempfile.mkdtemp(prefix="pt_aot_bench_")
    try:
        cold = _spawn_compile(d)
        warm = _spawn_compile(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    if not cold or not warm:
        return {"error": "compile bench worker failed",
                "cold": bool(cold), "warm": bool(warm)}
    # cold-start overhead for a fresh process: program build + the
    # startup run + the main run's first results, MINUS one
    # steady-state step (the real train-step compute both sides pay
    # identically — leaving it in lets a fast machine's shrinking
    # compile time drown in the shared compute floor). The main run
    # alone also understates the cold cost: the startup program
    # recompiles too.
    def overhead(r):
        return max(0.001, r["build_s"] + r["startup_s"]
                   + r["first_results_s"] - r.get("steady_s", 0.0))

    cold_s, warm_s = overhead(cold), overhead(warm)
    speedup = cold_s / warm_s if warm_s > 0 else None
    return {
        "backend": "cpu", "program": cold.get("program"),
        "cold_compile_s": round(cold_s, 3),
        "warm_compile_s": round(warm_s, 3),
        "cold_parts": {k: cold[k] for k in
                       ("build_s", "startup_s", "first_results_s",
                        "steady_s")},
        "warm_parts": {k: warm[k] for k in
                       ("build_s", "startup_s", "first_results_s",
                        "steady_s")},
        "speedup": round(speedup, 2) if speedup else None,
        "warm_trace_cache_hit": warm["trace_hit"] > 0,
        "fetch_bitwise_identical":
            cold["fetch_sha256"] == warm["fetch_sha256"],
    }


def _build_fc3(B, H):
    """The pipeline/observability bench workload: a 3-layer fc train
    program (shared so the two blocks' steps/s numbers compare)."""
    import paddle_tpu as pt
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", [H])
        y = pt.layers.data("y", [1])
        h1 = pt.layers.fc(x, H, act="relu")
        h2 = pt.layers.fc(h1, H, act="relu")
        pred = pt.layers.fc(h2, 1)
        loss = pt.layers.mean(pt.layers.square_error_cost(pred, y))
        pt.optimizer.SGD(0.01).minimize(loss, startup_program=startup,
                                        program=main)
    main.random_seed = 7
    startup.random_seed = 7
    return main, startup, loss


def bench_pipeline():
    """sync-vs-pipelined `train_from_dataset` block (ISSUE 2, docs/
    async_pipeline.md): one input-bound static train program run twice
    through the SAME compiled executable — once with
    FLAGS_executor_inflight_steps=1 (the old dispatch->sync->dispatch
    loop) and once with the default bounded window (dispatch-ahead +
    background feed staging + off-critical-path drains). Host work
    (batch synthesis + device_put staging + fetch materialization) is
    deliberately inside the timed loop: that is the per-step overhead
    the pipeline overlaps with device execution. CPU numbers are real —
    XLA:CPU executes on background threads, so the overlap exists
    off-TPU too — and the fetch digests prove the fast loop computes
    bitwise-identical results."""
    import hashlib
    import paddle_tpu as pt
    from paddle_tpu.flags import get_flags

    B, H, steps, io_s = 64, 640, 60, 0.005
    main, startup, loss = _build_fc3(B, H)

    # the batch pool is synthesized ONCE, outside every timed region:
    # the generator then models a latency-bound reader (disk/network
    # wait per batch, cheap hand-off) — the common real input pipeline.
    # The sync loop serializes that wait with the device step; the
    # pipelined loop hides it behind in-flight compute (the prefetcher
    # thread blocks on it while the device runs)
    rng = np.random.RandomState(0)
    pool = [{"x": rng.rand(B, H).astype(np.float32),
             "y": rng.rand(B, 1).astype(np.float32)}
            for _ in range(steps)]

    def batches(n):
        for i in range(n):
            time.sleep(io_s)
            yield pool[i % steps]

    exe = pt.Executor()
    saved = get_flags(["FLAGS_executor_inflight_steps"])
    try:
        # warmup/compile on a throwaway scope: the in-flight window is
        # not a lowering flag, so both timed runs share this executable
        wscope = pt.Scope()
        with pt.scope_guard(wscope):
            exe.run(startup)
            exe.train_from_dataset(program=main, dataset=batches(2),
                                   fetch_list=[loss])

        def timed(window):
            pt.set_flags({"FLAGS_executor_inflight_steps": window})
            scope = pt.Scope()
            with pt.scope_guard(scope):
                exe.run(startup)
                t0 = time.time()
                res = exe.train_from_dataset(program=main,
                                             dataset=batches(steps),
                                             fetch_list=[loss])
                dt = time.time() - t0  # includes the final drain
            digest = hashlib.sha256(
                b"".join(np.ascontiguousarray(o).tobytes()
                         for r in res for o in r)).hexdigest()
            return steps / dt, digest

        window = max(2, int(saved.get("FLAGS_executor_inflight_steps", 2)
                            or 2))
        # best-of-3 per mode: the first run in a fresh process pays
        # thread-pool/allocator warmup, and on small containers the
        # scheduler jitters individual runs — best-of is the steady state
        reps = [(timed(1), timed(window)) for _ in range(3)]
        sync_sps, sync_digest = max((s for s, _ in reps),
                                    key=lambda r: r[0])
        pipe_sps, pipe_digest = max((p for _, p in reps),
                                    key=lambda r: r[0])
        digests = {d for pair in reps for (_, d) in pair}
    finally:
        pt.set_flags(saved)
    return {
        "workload": "fc3-H%d-B%d x%d steps (input-bound: %.1fms "
                    "simulated read latency/batch, SGD)"
                    % (H, B, steps, io_s * 1e3),
        "window": window,
        "sync_steps_per_sec": round(sync_sps, 1),
        "pipelined_steps_per_sec": round(pipe_sps, 1),
        "speedup": round(pipe_sps / sync_sps, 2),
        "fetch_bitwise_identical": len(digests) == 1,
    }


def bench_observability():
    """telemetry-overhead block (ISSUE 3, docs/observability.md): the
    SAME pipelined train_from_dataset workload as bench_pipeline, run
    with FLAGS_telemetry off (the instrumented code's disabled fast
    path — directly comparable to the pipeline block's
    pipelined_steps_per_sec and to earlier rounds' BENCH artifacts)
    and with telemetry on (spans + timers + flight recorder live).
    Also proves the step-correlation contract on the exported chrome
    trace, validates the Prometheus export, and carries the counter
    deltas of the telemetry-on run via tools/stat_diff.py."""
    import json as _json
    import re
    import tempfile
    import paddle_tpu as pt
    from paddle_tpu import monitor, profiler, telemetry
    from paddle_tpu.flags import get_flags
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import stat_diff

    B, H, steps, io_s = 64, 640, 60, 0.005
    main, startup, loss = _build_fc3(B, H)
    rng = np.random.RandomState(0)
    pool = [{"x": rng.rand(B, H).astype(np.float32),
             "y": rng.rand(B, 1).astype(np.float32)}
            for _ in range(steps)]

    def batches(n):
        for i in range(n):
            time.sleep(io_s)
            yield pool[i % steps]

    exe = pt.Executor()
    saved = get_flags(["FLAGS_executor_inflight_steps",
                       "FLAGS_telemetry"])
    try:
        window = max(2, int(saved.get("FLAGS_executor_inflight_steps", 2)
                            or 2))
        pt.set_flags({"FLAGS_executor_inflight_steps": window,
                      "FLAGS_telemetry": False})
        wscope = pt.Scope()
        with pt.scope_guard(wscope):
            exe.run(startup)
            exe.train_from_dataset(program=main, dataset=batches(2),
                                   fetch_list=[loss])

        def timed(telemetry_on):
            pt.set_flags({"FLAGS_telemetry": telemetry_on})
            scope = pt.Scope()
            with pt.scope_guard(scope):
                exe.run(startup)
                t0 = time.time()
                exe.train_from_dataset(program=main,
                                       dataset=batches(steps),
                                       fetch_list=[loss],
                                       keep_results=False)
                return steps / (time.time() - t0)

        # best-of-3 per mode (same rationale as bench_pipeline)
        snap0 = monitor.snapshot()
        off_sps = max(timed(False) for _ in range(3))
        snap1 = monitor.snapshot()
        profiler.reset_profiler()
        telemetry.flight_reset()
        on_sps = max(timed(True) for _ in range(3))
        snap2 = monitor.snapshot()
        flight_depth = len(telemetry.flight_records())
        # introspection-server block (PR 7): the SAME workload with
        # the flag-off fast path, while a scraper thread hammers
        # /metrics on an ephemeral-port server — scrape overhead on
        # the pipelined loop is the <=1% acceptance gate. Runs after
        # snap2 so its counters don't contaminate the on/off deltas.
        introspect_detail = _bench_introspect_scrape(timed)
    finally:
        pt.set_flags(saved)

    def counter_delta(a, b):
        return {k: b["counters"].get(k, 0.0) - a["counters"].get(k, 0.0)
                for k in b["counters"]
                if b["counters"].get(k, 0.0) != a["counters"].get(k, 0.0)}

    # the off and on runs do IDENTICAL work, so their counter deltas
    # must match: telemetry adding syncs/misses/evictions would show
    # here as a stat_diff regression of the on-delta over the off-delta
    delta_off = counter_delta(snap0, snap1)
    delta_on = counter_delta(snap1, snap2)

    # step-correlation proof: the exported chrome trace must show
    # dispatch/feed-stage/drain spans sharing a step id
    correlated = False
    try:
        fd, tpath = tempfile.mkstemp(suffix=".json")
        os.close(fd)
        try:
            profiler.export_chrome_tracing(tpath)
            with open(tpath) as f:
                trace = _json.load(f)["traceEvents"]
        finally:
            os.unlink(tpath)
        by_step = {}
        for e in trace:
            step = (e.get("args") or {}).get("step")
            if e.get("ph") == "X" and step is not None:
                by_step.setdefault(step, set()).add(e["name"])
        correlated = any({"pipeline/dispatch", "pipeline/drain",
                          "pipeline/feed_stage"} <= names
                         for names in by_step.values())
    except Exception as e:
        print("WARN: trace correlation check failed: %r" % (e,),
              file=sys.stderr)

    prom = monitor.to_prometheus()
    prom_re = re.compile(
        r"^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eEinfa]+)$")
    prom_valid = all(prom_re.match(ln) for ln in prom.splitlines() if ln)

    d = stat_diff.diff_snapshots({"counters": delta_off},
                                 {"counters": delta_on})
    overhead = (1.0 - on_sps / off_sps) * 100.0 if off_sps else None
    return {
        "workload": "fc3-H%d-B%d x%d steps (%.1fms read latency/batch, "
                    "pipelined window=%d) — same as the pipeline block"
                    % (H, B, steps, io_s * 1e3, window),
        "telemetry_off_steps_per_sec": round(off_sps, 1),
        "telemetry_on_steps_per_sec": round(on_sps, 1),
        "enabled_overhead_pct": round(overhead, 2)
        if overhead is not None else None,
        "trace_step_correlated": correlated,
        "prometheus_valid": prom_valid,
        "flight_recorder_steps": flight_depth,
        "stat_deltas_per_run_counters": {
            k: v for k, v in sorted(delta_on.items())[:12]},
        "stat_regressions_on_vs_off": stat_diff.find_regressions(d),
        "introspect": introspect_detail,
    }


def _bench_introspect_scrape(timed):
    """Measure the introspection server under scrape load: start on an
    ephemeral port, point a 2 Hz /metrics scraper at it (30x denser
    than Prometheus' default 15s interval — an unthrottled loop just
    measures GIL contention against the pure-python host loop, not
    scraping), re-run the telemetry-off pipelined workload, smoke
    every endpoint, and validate the exposition families. Never
    fatal — the observability block's headline numbers don't depend
    on it."""
    import re
    import threading
    import urllib.error
    import urllib.request
    from paddle_tpu import introspect
    try:
        srv = introspect.start(port=0)
        stop_evt = threading.Event()
        paused = threading.Event()
        scrapes = [0]

        def scrape_loop():
            while not stop_evt.is_set():
                if not paused.is_set():
                    try:
                        urllib.request.urlopen(
                            srv.url + "/metrics", timeout=2).read()
                        scrapes[0] += 1
                    except Exception:
                        pass
                stop_evt.wait(0.5)

        th = threading.Thread(target=scrape_loop, daemon=True)
        th.start()
        # interleaved baseline/scraped pairs, best-of-5 each: the
        # workload's run-to-run jitter (~10-15%) dwarfs a 1% effect,
        # and interleaving + max statistics cancels the slow drift a
        # sequential A-then-B comparison would read as overhead
        base_runs, scraped_runs = [], []
        try:
            for _ in range(5):
                paused.set()
                base_runs.append(timed(False))
                paused.clear()
                scraped_runs.append(timed(False))
        finally:
            stop_evt.set()
            th.join(timeout=10.0)
        base_sps, scraped_sps = max(base_runs), max(scraped_runs)
        endpoints = {}
        for ep in ("/healthz", "/readyz", "/statusz", "/programz",
                   "/flightz"):
            try:
                endpoints[ep] = urllib.request.urlopen(
                    srv.url + ep, timeout=5).status
            except urllib.error.HTTPError as e:
                endpoints[ep] = e.code  # /readyz may be 503, still live
        body = urllib.request.urlopen(
            srv.url + "/metrics", timeout=5).read().decode()
        families = re.findall(r"^# TYPE (\S+) (\S+)$", body, re.M)
        overhead = ((1.0 - scraped_sps / base_sps) * 100.0
                    if base_sps else None)
        # deterministic per-scrape cost: CPU seconds stolen per
        # /metrics render, measured directly — the A/B delta above
        # bottoms out at the workload's jitter floor (~2%), while this
        # converts exactly to overhead at any scrape interval
        c0 = time.process_time()
        n_cost = 30
        for _ in range(n_cost):
            urllib.request.urlopen(srv.url + "/metrics",
                                   timeout=5).read()
        cpu_ms = (time.process_time() - c0) / n_cost * 1e3
        return {
            "baseline_steps_per_sec": round(base_sps, 1),
            "scraped_steps_per_sec": round(scraped_sps, 1),
            "measured_delta_pct": round(overhead, 2)
            if overhead is not None else None,
            "scrape_cpu_ms": round(cpu_ms, 3),
            "scrape_overhead_pct_at_15s_interval": round(
                cpu_ms / 1e3 / 15.0 * 100.0, 4),
            "scrapes_completed": scrapes[0],
            "endpoints": endpoints,
            "metric_families": len(families),
            "families_all_typed": bool(families) and all(
                t in ("counter", "gauge", "summary")
                for _, t in families),
        }
    except Exception as e:
        print("WARN: introspect bench failed: %r" % (e,),
              file=sys.stderr)
        return {"error": repr(e)}
    finally:
        try:
            introspect.stop()
        except Exception:
            pass


def bench_serving():
    """serving block (ISSUE 4, docs/serving.md): concurrent variable-
    batch inference over one saved model through three front-ends —
    naive (a lock-guarded shared Predictor, exact shapes, one dispatch
    per request: the pre-PR-4 concurrency story), bucketed (shape-
    bucketed Predictor, still per-request), and pooled (PredictorPool:
    dynamic micro-batching + bucketing). Every mode is fully warmed
    before its timed pass, so the deltas isolate steady-state dispatch
    and batching cost rather than compiles; STAT_executor_compile
    deltas pin zero steady-state recompiles, and the pooled outputs
    are checked bitwise against serial execution (row independence on
    XLA — tests/test_serving.py)."""
    import shutil
    import tempfile
    import threading
    import paddle_tpu as pt
    from paddle_tpu import serving, tracing
    from paddle_tpu.flags import set_flags
    from paddle_tpu.monitor import stat_get, timer_get

    T, R, H_IN = 8, 240, 32
    model_dir = tempfile.mkdtemp(prefix="pt_serving_bench_")
    try:
        # a DEEP stack of small layers: per-request cost is dominated
        # by fixed per-op/dispatch overhead, nearly independent of the
        # row count — the regime (kernel-launch-bound serving) where
        # micro-batching pays. One wide matmul would be row-bound and
        # batching could only ever tie.
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", [H_IN])
            h = x
            for _ in range(24):
                h = pt.layers.fc(h, 64, act="relu")
            y = pt.layers.fc(h, 8)
        exe = pt.Executor()
        exe.run(startup)
        pt.io.save_inference_model(model_dir, ["x"], [y], exe,
                                   main_program=main)

        # fixed request stream: batch sizes 1..8 (the variable-length
        # traffic shape that defeats exact-shape compilation caches)
        rng = np.random.RandomState(0)
        sizes = rng.randint(1, 9, size=R)
        reqs = [rng.rand(int(b), H_IN).astype(np.float32) for b in sizes]
        total_rows = int(sizes.sum())

        def predictor(bucketed):
            cfg = pt.inference.Config(model_dir)
            if bucketed:
                cfg.switch_shape_bucketing(True, buckets="pow2:32")
            return pt.inference.create_predictor(cfg)

        # serial reference outputs (exact shapes, no concurrency) —
        # the bitwise ground truth every mode must reproduce
        ref = predictor(bucketed=False)
        expected = [np.asarray(ref.run([r])[0]) for r in reqs]

        def clients(call):
            """T closed-loop client threads splitting the R-request
            stream; returns (wall_s, per-request latencies, outputs)."""
            lat, outs = [0.0] * R, [None] * R

            def worker(tid):
                for i in range(tid, R, T):
                    t0 = time.perf_counter()
                    outs[i] = np.asarray(call(i))
                    lat[i] = time.perf_counter() - t0

            threads = [threading.Thread(target=worker, args=(t,))
                       for t in range(T)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return time.perf_counter() - t0, lat, outs

        def p95_ms(lat):
            return round(sorted(lat)[int(0.95 * len(lat))] * 1e3, 3)

        report, parity = {}, {}

        # --- naive: shared exact-shape Predictor behind a lock --------
        naive = predictor(bucketed=False)
        for b in sorted(set(int(s) for s in sizes)):  # warm every shape
            naive.run([np.zeros((b, H_IN), np.float32)])
        lock = threading.Lock()

        def naive_call(i):
            with lock:
                return naive.run([reqs[i]])[0]

        c0 = stat_get("STAT_executor_compile")
        wall, lat, outs = min((clients(naive_call) for _ in range(2)),
                              key=lambda r: r[0])
        report["naive"] = {
            "rows_per_sec": round(total_rows / wall, 1),
            "p95_ms": p95_ms(lat),
            "steady_state_recompiles":
                int(stat_get("STAT_executor_compile") - c0)}
        parity["naive"] = all(np.array_equal(o, e)
                              for o, e in zip(outs, expected))

        # --- bucketed: padded shapes, still one dispatch/request ------
        bucketed = predictor(bucketed=True)
        bucketed.warmup_buckets([np.zeros((1, H_IN), np.float32)])
        block = threading.Lock()

        def bucketed_call(i):
            with block:
                return bucketed.run([reqs[i]])[0]

        c0 = stat_get("STAT_executor_compile")
        wall, lat, outs = min((clients(bucketed_call) for _ in range(2)),
                              key=lambda r: r[0])
        report["bucketed"] = {
            "rows_per_sec": round(total_rows / wall, 1),
            "p95_ms": p95_ms(lat),
            "steady_state_recompiles":
                int(stat_get("STAT_executor_compile") - c0)}
        parity["bucketed"] = all(np.array_equal(o, e)
                                 for o, e in zip(outs, expected))

        # --- pooled: micro-batched + bucketed -------------------------
        with serving.PredictorPool(predictor(bucketed=True),
                                   max_batch=32) as pool:
            pool.warmup([np.zeros((1, H_IN), np.float32)])
            b0 = stat_get("STAT_serving_batches")
            r0 = stat_get("STAT_serving_batched_rows")
            pad0 = stat_get("STAT_predictor_pad_rows")
            c0 = stat_get("STAT_executor_compile")
            tc0 = stat_get("STAT_trace_completed")
            nm0 = stat_get("STAT_trace_nonmonotonic")
            wall, lat, outs = min((clients(
                lambda i: pool.run([reqs[i]])[0]) for _ in range(2)),
                key=lambda r: r[0])
            batches = stat_get("STAT_serving_batches") - b0
            rows = stat_get("STAT_serving_batched_rows") - r0
            report["pooled"] = {
                "rows_per_sec": round(total_rows / wall, 1),
                "p95_ms": p95_ms(lat),
                "steady_state_recompiles":
                    int(stat_get("STAT_executor_compile") - c0),
                "executed_batches": int(batches),
                "mean_batch_rows":
                    round(rows / batches, 1) if batches else None,
                "padded_rows": int(
                    stat_get("STAT_predictor_pad_rows") - pad0)}

            # --- request tracing: latency decomposition + overhead ----
            # every pooled request must have produced one complete,
            # monotonically ordered trace (2 client passes of R each)
            def _pcts(timer):
                st = timer_get(timer)
                if not st["count"]:
                    return None
                return {"p50_us": round(st["p50"], 1),
                        "p95_us": round(st["p95"], 1)}

            sample = tracing.recent()[-1]
            offs = [t for _, t in sample["stages"]]
            report["tracing"] = {
                "traces_completed": int(
                    stat_get("STAT_trace_completed") - tc0),
                "expected_traces": 2 * R,
                "all_complete": int(stat_get("STAT_trace_completed")
                                    - tc0) == 2 * R,
                "nonmonotonic": int(
                    stat_get("STAT_trace_nonmonotonic") - nm0),
                "sample_stages": [s for s, _ in sample["stages"]],
                "sample_monotonic": offs == sorted(offs),
                "queue_wait": _pcts("TIMER_serving_queue_wait_us"),
                "execute": _pcts("TIMER_serving_execute_us"),
                "total": _pcts("TIMER_serving_total_us"),
            }

            # tracing-on-vs-off overhead, same interleaved best-of
            # methodology as the PR 7 scrape-cost block: run-to-run
            # jitter dwarfs a <1% effect, so interleave the pairs and
            # compare the max of each arm
            on_runs, off_runs = [], []
            try:
                for _ in range(5):
                    set_flags({"FLAGS_request_tracing": False})
                    w, _, _ = clients(
                        lambda i: pool.run([reqs[i]])[0])
                    off_runs.append(total_rows / w)
                    set_flags({"FLAGS_request_tracing": True})
                    w, _, _ = clients(
                        lambda i: pool.run([reqs[i]])[0])
                    on_runs.append(total_rows / w)
            finally:
                set_flags({"FLAGS_request_tracing": True})
            off_rps, on_rps = max(off_runs), max(on_runs)
            report["tracing"]["overhead"] = {
                "tracing_off_rows_per_sec": round(off_rps, 1),
                "tracing_on_rows_per_sec": round(on_rps, 1),
                "overhead_pct": round((1.0 - on_rps / off_rps) * 100.0,
                                      2),
                # the honest unit: added wall per request. The percent
                # above is GIL-amplified on this CPU bench — requests
                # here finish in ~1ms, so ~10us of pure-Python trace
                # bookkeeping reads as several percent; against real
                # serving latencies the same microseconds are <1%
                # (docs/observability.md).
                "overhead_us_per_request": round(
                    (total_rows / on_rps - total_rows / off_rps)
                    / R * 1e6, 1),
            }
        parity["pooled"] = all(np.array_equal(o, e)
                               for o, e in zip(outs, expected))
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)

    naive_sps = report["naive"]["rows_per_sec"]
    return {
        "workload": "fc25-H64 inference (in=%d): %d client threads, "
                    "%d requests, batch sizes 1..8 (%d rows)"
                    % (H_IN, T, R, total_rows),
        **report,
        "speedup_pooled_vs_naive":
            round(report["pooled"]["rows_per_sec"] / naive_sps, 2),
        "speedup_bucketed_vs_naive":
            round(report["bucketed"]["rows_per_sec"] / naive_sps, 2),
        "p95_improved":
            report["pooled"]["p95_ms"] < report["naive"]["p95_ms"],
        "outputs_bitwise_identical": all(parity.values()),
    }


def bench_generation():
    """generation block (ISSUE 5, docs/generation.md): autoregressive
    decode through two engines over the same mixed request stream —
    naive (full-context redecode of every sequence at every token: the
    no-KV-cache story) and paged (GenerationEngine: paged KV cache +
    continuous batching at fixed decode width). Both use the SAME
    sampler and fixed attention lane count, so the streams must match
    token for token (the bitwise parity gate from tests/
    test_generation.py); STAT_generation_compile pins zero steady-state
    recompiles, and a tools/stat_diff.py pass flags decode-step p95
    regressions against the previous run's persisted snapshot."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import stat_diff
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest,
                                       NaiveGenerator, SamplingParams,
                                       init_params)
    from paddle_tpu import monitor
    from paddle_tpu.flags import set_flags
    from paddle_tpu.monitor import stat_get, timer_get

    cfg = DecoderConfig(vocab_size=128, hidden=64, layers=4, heads=4,
                        max_seq_len=128)
    params = init_params(cfg, seed=0)
    eng = GenerationEngine(cfg, params, num_blocks=256, block_size=8,
                           decode_width=8)

    rng = np.random.RandomState(0)
    R = 24
    reqs = []
    for i in range(R):
        plen = int(rng.randint(4, 29))
        reqs.append(GenerationRequest(
            prompt=list(rng.randint(1, cfg.vocab_size, size=plen)),
            max_new_tokens=int(rng.randint(16, 33)),
            sampling=SamplingParams(
                temperature=0.8 if i % 2 else 0.0,
                top_k=16 if i % 3 == 0 else 0, seed=i),
            request_id=i))
    total_new = sum(r.max_new_tokens for r in reqs)

    # --- naive: full-context redecode per token, one request at a time
    naive = NaiveGenerator(cfg, params, buckets="pow2:32",
                           attn_lanes=eng.attn_lanes)
    expected = {}
    expected[reqs[0].request_id] = naive.generate(reqs[0])  # warm
    t0 = time.perf_counter()
    for r in reqs:
        expected[r.request_id] = naive.generate(r)
    naive_wall = time.perf_counter() - t0
    naive_tps = total_new / naive_wall

    # --- paged: continuous batching at fixed width ---------------------
    eng.warmup()
    c0 = stat_get("STAT_generation_compile")
    tc0 = stat_get("STAT_trace_completed")
    nm0 = stat_get("STAT_trace_nonmonotonic")
    snap0 = monitor.snapshot()
    for r in reqs:
        eng.submit(r)
    step_s, done = [], []
    t0 = time.perf_counter()
    while not eng.idle:
        ts = time.perf_counter()
        done.extend(eng.step())
        step_s.append(time.perf_counter() - ts)
    paged_wall = time.perf_counter() - t0
    paged_tps = total_new / paged_wall
    recompiles = int(stat_get("STAT_generation_compile") - c0)
    results = {r.request_id: r for r in done}
    parity = all(results[i].tokens == expected[i].tokens
                 for i in range(R))
    p95_ms = round(sorted(step_s)[int(0.95 * len(step_s))] * 1e3, 3)

    # --- request tracing: every submitted request yields one complete
    # trace; TTFT/TPOT/queue-wait come from the trace timers ----------
    def _pcts(timer):
        st = timer_get(timer)
        if not st["count"]:
            return None
        return {"p50_us": round(st["p50"], 1),
                "p95_us": round(st["p95"], 1)}

    from paddle_tpu import tracing as _tracing
    sample = _tracing.recent()[-1] if _tracing.recent() else None
    trace_report = {
        "traces_completed": int(stat_get("STAT_trace_completed") - tc0),
        "expected_traces": R,
        "all_complete":
            int(stat_get("STAT_trace_completed") - tc0) == R,
        "nonmonotonic": int(
            stat_get("STAT_trace_nonmonotonic") - nm0),
        "sample_stages": ([s for s, _ in sample["stages"]]
                          if sample else None),
        "ttft": _pcts("TIMER_generation_ttft_us"),
        "tpot": _pcts("TIMER_generation_tpot_us"),
        "queue_wait": _pcts("TIMER_generation_queue_wait_us"),
    }

    # --- stat_diff: decode-step p95 vs the previous run's snapshot ----
    keep = lambda name: "generation" in name  # noqa: E731
    snap1 = monitor.snapshot()
    cur = {
        "counters": {k: v for k, v in snap1["counters"].items()
                     if keep(k)},
        "gauges": {},
        "timers": {k: v for k, v in snap1["timers"].items()
                   if keep(k)},
    }
    snap_path = os.environ.get(
        "PT_GENERATION_BENCH_SNAPSHOT",
        os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                     "bench_generation_last.json"))
    regressions = []
    try:
        prev = stat_diff.load_snapshot(snap_path)
        regressions = stat_diff.find_regressions(
            stat_diff.diff_snapshots(prev, cur), threshold_pct=25.0)
        # only latency regressions gate; counter volume follows the
        # workload definition, which this block fixes anyway
        regressions = [r for r in regressions if r.startswith("timer")]
    except OSError:
        pass  # first run: nothing to compare against
    try:
        os.makedirs(os.path.dirname(snap_path), exist_ok=True)
        with open(snap_path, "w") as f:
            json.dump(cur, f)
    except OSError:
        pass
    del snap0  # per-run deltas live in the persisted snapshot diff

    # --- tracing on-vs-off overhead (interleaved, AFTER the stat_diff
    # snapshot so the extra passes never perturb the gated timers) ----
    def paged_pass():
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        drained = []
        while not eng.idle:
            drained.extend(eng.step())
        return total_new / (time.perf_counter() - t0)

    on_runs, off_runs = [], []
    try:
        for _ in range(2):
            set_flags({"FLAGS_request_tracing": False})
            off_runs.append(paged_pass())
            set_flags({"FLAGS_request_tracing": True})
            on_runs.append(paged_pass())
    finally:
        set_flags({"FLAGS_request_tracing": True})
    off_tps, on_tps = max(off_runs), max(on_runs)
    trace_report["overhead"] = {
        "tracing_off_tokens_per_sec": round(off_tps, 1),
        "tracing_on_tokens_per_sec": round(on_tps, 1),
        "overhead_pct": round((1.0 - on_tps / off_tps) * 100.0, 2),
        # per-token cost in wall time — the unit that transfers to
        # real decode-step latencies (docs/observability.md)
        "overhead_us_per_token": round(
            (1.0 / on_tps - 1.0 / off_tps) * 1e6, 2),
    }

    return {
        "workload": "decoder L%d-H%d (vocab %d): %d requests, "
                    "prompts 4..28, %d new tokens"
                    % (cfg.layers, cfg.hidden, cfg.vocab_size, R,
                       total_new),
        "naive_tokens_per_sec": round(naive_tps, 1),
        "paged_tokens_per_sec": round(paged_tps, 1),
        "speedup_paged_vs_naive": round(paged_tps / naive_tps, 2),
        "p95_decode_step_ms": p95_ms,
        "steady_state_recompiles": recompiles,
        "tokens_bitwise_identical": bool(parity),
        "decode_step_p95_regressions": regressions,
        "tracing": trace_report,
    }


def bench_generation_prefix():
    """prefix-cache generation block (ISSUE 14, docs/generation.md):
    cache-on vs cache-off chunked engines over the SAME agent-style
    request stream — every prompt opens with one shared 96-token
    system prefix (two full 48-token chunks) followed by a short
    unique suffix. The cache-on engine PERSISTS its PrefixCache across
    passes, so after the cold first pass every admission walks the
    cached chunk chain and starts prefill at the suffix; the cache-off
    engine recomputes the prefix every time.

    Gates (ISSUE 14 acceptance): cache-on TTFT p95 >= 2x lower than
    cache-off, zero steady-state recompiles (admission through the
    cache reuses the same mixed + COW executables), streams
    bitwise-identical between the two engines keyed by request_id.
    TIMER_generation_prefix_admit_us rides the persisted-snapshot
    stat_diff gate (PT_GENERATION_PREFIX_BENCH_SNAPSHOT)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import stat_diff
    from dataclasses import replace
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest,
                                       SamplingParams, init_params)
    from paddle_tpu import monitor
    from paddle_tpu import tracing as _tracing
    from paddle_tpu.monitor import gauge_get, stat_get

    cfg = DecoderConfig(vocab_size=128, hidden=64, layers=4, heads=4,
                        max_seq_len=128)
    params = init_params(cfg, seed=0)

    rng = np.random.RandomState(14)
    # the shared "system prompt": 96 tokens = two full 48-token chunks
    # (chunk-aligned boundaries — the cache's hash unit). 16 requests
    # over 8 lanes = two admission waves: enough queueing to be a real
    # serving shape, little enough that the p95 TTFT still reflects
    # the prefill compute the cache removes rather than queue delay.
    system = list(rng.randint(1, cfg.vocab_size, size=96))
    R = 16
    reqs = []
    for i in range(R):
        suffix = list(rng.randint(1, cfg.vocab_size,
                                  size=int(rng.randint(3, 7))))
        reqs.append(GenerationRequest(
            prompt=system + suffix,
            max_new_tokens=int(rng.randint(3, 6)),
            sampling=SamplingParams(
                temperature=0.8 if i % 2 else 0.0,
                top_k=16 if i % 3 == 0 else 0, seed=i),
            request_id=i))
    total_new = sum(r.max_new_tokens for r in reqs)

    def _pct(xs, p):
        if not xs:
            return None
        return round(sorted(xs)[int(p * (len(xs) - 1))], 1)

    def run_pass(eng):
        traces = {}
        for r in reqs:
            tr = _tracing.begin("generation")
            traces[r.request_id] = tr
            eng.submit(replace(r, trace=tr))
        done = []
        t0 = time.perf_counter()
        while not eng.idle:
            done.extend(eng.step())
        wall = time.perf_counter() - t0
        return wall, traces, done

    def report(best):
        wall, traces, done = best
        ttfts = []
        for tr in traces.values():
            if getattr(tr, "t_first_token", None) is None:
                continue
            ttfts.append((tr.t_first_token - tr.t0) * 1e6)
        return {
            "tokens_per_sec": round(total_new / wall, 1),
            "ttft_us": {"p50": _pct(ttfts, 0.5),
                        "p95": _pct(ttfts, 0.95)},
        }, {res.request_id: res.tokens for res in done}

    # interleaved best-of-4 for the same reason as the mixed block:
    # a ratio gate needs both engines sampling the same CPU-drift
    # windows. The cache-on engine keeps its cache across passes —
    # pass 1 is its cold pass and best-of-4 reports its WARM steady
    # state, which is exactly the serving regime the cache targets.
    mk = lambda **kw: GenerationEngine(  # noqa: E731
        cfg, params, num_blocks=256, block_size=8, decode_width=8,
        prefill_chunk=48, token_budget=104, **kw)
    off_eng = mk(prefix_cache=False)
    on_eng = mk(prefix_cache=True)
    off_eng.warmup()
    on_eng.warmup()
    c0 = stat_get("STAT_generation_compile")
    h0 = stat_get("STAT_generation_prefix_hits")
    m0 = stat_get("STAT_generation_prefix_misses")
    w0 = stat_get("STAT_generation_prefix_cow_copies")
    off_best = on_best = None
    for _ in range(4):
        for eng, which in ((off_eng, "off"), (on_eng, "on")):
            got = run_pass(eng)
            if which == "off":
                if off_best is None or got[0] < off_best[0]:
                    off_best = got
            else:
                if on_best is None or got[0] < on_best[0]:
                    on_best = got
    recompiles = int(stat_get("STAT_generation_compile") - c0)
    off_rep, off_tokens = report(off_best)
    on_rep, on_tokens = report(on_best)
    on_rep["prefix_hits"] = int(
        stat_get("STAT_generation_prefix_hits") - h0)
    on_rep["prefix_misses"] = int(
        stat_get("STAT_generation_prefix_misses") - m0)
    on_rep["cow_copies"] = int(
        stat_get("STAT_generation_prefix_cow_copies") - w0)
    on_rep["kv_blocks_saved"] = int(gauge_get("GAUGE_kv_blocks_saved"))

    parity = off_tokens == on_tokens and len(on_tokens) == R

    keep = lambda name: "generation" in name  # noqa: E731
    snap = monitor.snapshot()
    cur = {
        "counters": {k: v for k, v in snap["counters"].items()
                     if keep(k)},
        "gauges": {},
        "timers": {k: v for k, v in snap["timers"].items()
                   if keep(k)},
    }
    snap_path = os.environ.get(
        "PT_GENERATION_PREFIX_BENCH_SNAPSHOT",
        os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                     "bench_generation_prefix_last.json"))
    regressions = []
    try:
        prev = stat_diff.load_snapshot(snap_path)
        regressions = stat_diff.find_regressions(
            stat_diff.diff_snapshots(prev, cur), threshold_pct=25.0)
        regressions = [r for r in regressions if r.startswith("timer")]
    except OSError:
        pass  # first run: nothing to compare against
    try:
        os.makedirs(os.path.dirname(snap_path), exist_ok=True)
        with open(snap_path, "w") as f:
            json.dump(cur, f)
    except OSError:
        pass

    ttft_ratio = round(off_rep["ttft_us"]["p95"]
                       / on_rep["ttft_us"]["p95"], 2)
    return {
        "workload": "decoder L%d-H%d: %d requests, 96-token shared "
                    "prefix + 3..6 suffix, %d new tokens, width 8 "
                    "chunk 48 budget 104" % (cfg.layers, cfg.hidden,
                                             R, total_new),
        "cache_off": off_rep,
        "cache_on": on_rep,
        "ttft_p95_ratio_off_vs_on": ttft_ratio,
        "meets_ttft_2x": ttft_ratio >= 2.0,
        "speedup_tokens_per_sec": round(
            on_rep["tokens_per_sec"] / off_rep["tokens_per_sec"], 2),
        "steady_state_recompiles": recompiles,
        "tokens_bitwise_identical": bool(parity),
        "prefix_admit_p95_regressions": regressions,
    }


def bench_generation_spec():
    """speculative-decoding generation block (ISSUE 14,
    docs/generation.md): the ngram (prompt-lookup) drafter proposing
    k=3 tokens per decode lane per mixed step, verified in ONE pass of
    the same token_budget-slot executable, vs the identical engine
    with speculation off. Greedy requests over self-similar prompts —
    the regime prompt-lookup drafting targets (agent loops, code,
    retrieval-heavy serving).

    Gates (ISSUE 14 acceptance): streams bitwise-identical to plain
    decode, zero steady-state recompiles, tokens/s ratio >= 1.0
    HONESTLY measured — the draft is host-side and the verify slots
    ride a step the engine was already paying for, so on this CPU the
    ratio reflects real acceptance, not kernel-width accounting. The
    acceptance rate is reported so a regression in drafter quality is
    visible even while the ratio gate still passes."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import stat_diff
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest, init_params)
    from paddle_tpu import monitor
    from paddle_tpu.monitor import stat_get

    cfg = DecoderConfig(vocab_size=128, hidden=64, layers=4, heads=4,
                        max_seq_len=128)
    params = init_params(cfg, seed=0)

    rng = np.random.RandomState(21)
    R = 16
    reqs = []
    for i in range(R):
        # self-similar prompt: a short motif repeated — untrained
        # greedy decode settles into cycles the ngram drafter then
        # predicts, which is the honest analog of the repetitive
        # structure real speculative serving exploits
        motif = list(rng.randint(1, cfg.vocab_size, size=3))
        reqs.append(GenerationRequest(
            prompt=(motif * 13)[:int(rng.randint(34, 40))],
            max_new_tokens=24, request_id=i))
    total_new = sum(r.max_new_tokens for r in reqs)

    def run_pass(eng):
        for r in reqs:
            eng.submit(GenerationRequest(**r.__dict__))
        done = []
        t0 = time.perf_counter()
        while not eng.idle:
            done.extend(eng.step())
        wall = time.perf_counter() - t0
        return wall, {res.request_id: res.tokens for res in done}

    # prefix cache off in both: this block isolates speculation
    mk = lambda **kw: GenerationEngine(  # noqa: E731
        cfg, params, num_blocks=256, block_size=8, decode_width=8,
        prefill_chunk=48, prefix_cache=False, **kw)
    plain_eng = mk(spec_tokens=0)
    spec_eng = mk(spec_tokens=3, draft="ngram")
    plain_eng.warmup()
    spec_eng.warmup()
    c0 = stat_get("STAT_generation_compile")
    p0 = stat_get("STAT_generation_spec_proposed")
    a0 = stat_get("STAT_generation_spec_accepted")
    plain_best = spec_best = None
    plain_tokens = spec_tokens = None
    for _ in range(4):
        for eng, which in ((plain_eng, "plain"), (spec_eng, "spec")):
            wall, toks = run_pass(eng)
            if which == "plain":
                plain_tokens = toks
                if plain_best is None or wall < plain_best:
                    plain_best = wall
            else:
                spec_tokens = toks
                if spec_best is None or wall < spec_best:
                    spec_best = wall
    recompiles = int(stat_get("STAT_generation_compile") - c0)
    proposed = int(stat_get("STAT_generation_spec_proposed") - p0)
    accepted = int(stat_get("STAT_generation_spec_accepted") - a0)
    parity = plain_tokens == spec_tokens and len(spec_tokens) == R

    snap = monitor.snapshot()
    cur = {
        "counters": {k: v for k, v in snap["counters"].items()
                     if "generation" in k},
        "gauges": {},
        "timers": {k: v for k, v in snap["timers"].items()
                   if "generation" in k},
    }
    snap_path = os.environ.get(
        "PT_GENERATION_SPEC_BENCH_SNAPSHOT",
        os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                     "bench_generation_spec_last.json"))
    regressions = []
    try:
        prev = stat_diff.load_snapshot(snap_path)
        regressions = stat_diff.find_regressions(
            stat_diff.diff_snapshots(prev, cur), threshold_pct=25.0)
        regressions = [r for r in regressions if r.startswith("timer")]
    except OSError:
        pass
    try:
        os.makedirs(os.path.dirname(snap_path), exist_ok=True)
        with open(snap_path, "w") as f:
            json.dump(cur, f)
    except OSError:
        pass

    plain_tps = round(total_new / plain_best, 1)
    spec_tps = round(total_new / spec_best, 1)
    ratio = round(spec_tps / plain_tps, 2)
    return {
        "workload": "decoder L%d-H%d: %d greedy requests, "
                    "self-similar prompts 34..39, %d new tokens, "
                    "ngram drafter k=3" % (cfg.layers, cfg.hidden, R,
                                           total_new),
        "plain_tokens_per_sec": plain_tps,
        "spec_tokens_per_sec": spec_tps,
        "speedup_spec_vs_plain": ratio,
        "meets_1p0x": ratio >= 1.0,
        "proposed": proposed,
        "accepted": accepted,
        "acceptance_rate": round(accepted / proposed, 3)
        if proposed else None,
        "steady_state_recompiles": recompiles,
        "tokens_bitwise_identical": bool(parity),
        "mixed_step_p95_regressions": regressions,
    }


def bench_quantized_serving():
    """quantized serving block (ISSUE 15, docs/quantization.md): int8
    per-channel weights (int8 x int8 -> int32 -> scale matmuls) plus
    the int8 KV block pool with per-token-per-head scales dequantized
    inside the online-softmax loop, vs the identical fp32 engine.

    Error budget is measured the way the paper frames it — against the
    fp32 oracle on the SAME prompts: logit MSE, max-abs logit delta,
    and greedy-token agreement. Capacity is measured at a FIXED pool
    byte budget: each flavor gets as many blocks as fit, and the gate
    is the concurrent-sequence ratio (>= 2x, ISSUE 15 acceptance).
    Steady-state recompiles must be zero — the quantized executables
    live in the same AOT-cached bucketed/mixed program set, keyed by
    quant config in the fingerprint."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import stat_diff
    import jax.numpy as jnp
    from paddle_tpu import monitor, quant
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest, init_params)
    from paddle_tpu.generation.model import forward_full
    from paddle_tpu.monitor import stat_get

    cfg = DecoderConfig(vocab_size=128, hidden=64, layers=4, heads=4,
                        max_seq_len=128)
    params = init_params(cfg, seed=0)
    qparams = quant.quantize_decoder_params(params, "int8")

    # --- logit error budget vs the fp32 oracle -----------------------
    rng = np.random.RandomState(23)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(8, 48)),
                       jnp.int32)
    lens = jnp.asarray(rng.randint(1, 49, size=(8,)), jnp.int32)
    lf = np.asarray(forward_full(cfg, params, toks, lens)[0])
    lq = np.asarray(forward_full(cfg, qparams, toks, lens)[0])
    d = lf - lq
    max_abs = float(np.abs(d).max())
    mse = float((d ** 2).mean())
    greedy_agree = float((lf.argmax(-1) == lq.argmax(-1)).mean())

    # --- capacity at a fixed pool byte budget ------------------------
    bs = 8
    per_tok_f32 = 2 * cfg.layers * cfg.heads * (cfg.hidden //
                                                cfg.heads) * 4
    per_tok_i8 = per_tok_f32 // 4 + 2 * cfg.layers * cfg.heads * 4
    budget = 256 * bs * per_tok_f32          # 256 fp32 blocks' worth
    nb_f32 = budget // (bs * per_tok_f32)
    nb_i8 = budget // (bs * per_tok_i8)

    mk = lambda p, nb, **kw: GenerationEngine(  # noqa: E731
        cfg, p, num_blocks=int(nb), block_size=bs, decode_width=8,
        prefill_chunk=48, prefix_cache=False, **kw)
    f32_eng = mk(params, nb_f32)
    q_eng = mk(qparams, nb_i8, quant_mode="int8", kv_dtype="int8")
    cap_ratio = q_eng.kv_capacity_seqs() / max(
        f32_eng.kv_capacity_seqs(), 1)

    # --- throughput + stream agreement -------------------------------
    R = 16
    reqs = []
    for i in range(R):
        motif = list(rng.randint(1, cfg.vocab_size, size=3))
        reqs.append(GenerationRequest(
            prompt=(motif * 13)[:int(rng.randint(34, 40))],
            max_new_tokens=24, request_id=i))
    total_new = sum(r.max_new_tokens for r in reqs)

    def run_pass(eng):
        for r in reqs:
            eng.submit(GenerationRequest(**r.__dict__))
        done = []
        t0 = time.perf_counter()
        while not eng.idle:
            done.extend(eng.step())
        wall = time.perf_counter() - t0
        return wall, {res.request_id: res.tokens for res in done}

    f32_eng.warmup()
    q_eng.warmup()
    c0 = stat_get("STAT_generation_compile")
    b0 = stat_get("STAT_generation_kv_quant_blocks")
    f32_best = q_best = None
    f32_toks = q_toks = None
    for _ in range(4):
        for eng, which in ((f32_eng, "fp32"), (q_eng, "int8")):
            wall, t = run_pass(eng)
            if which == "fp32":
                f32_toks = t
                if f32_best is None or wall < f32_best:
                    f32_best = wall
            else:
                q_toks = t
                if q_best is None or wall < q_best:
                    q_best = wall
    recompiles = int(stat_get("STAT_generation_compile") - c0)
    kvq_blocks = int(stat_get("STAT_generation_kv_quant_blocks") - b0)
    agree = sum(f32_toks[i] == q_toks[i] for i in range(R))
    # agreed-prefix depth: one near-tie argmax flip diverges the rest
    # of an untrained model's stream, so whole-stream equality
    # understates agreement — the depth of the first divergence is the
    # honest stream-level error metric on long generations
    def _prefix(a, b):
        n = 0
        for x, y in zip(a, b):
            if x != y:
                break
            n += 1
        return n
    mean_prefix = sum(_prefix(f32_toks[i], q_toks[i])
                      for i in range(R)) / float(R)

    snap = monitor.snapshot()
    cur = {
        "counters": {k: v for k, v in snap["counters"].items()
                     if "generation" in k},
        "gauges": {k: v for k, v in snap["gauges"].items()
                   if "quant" in k or "kv_" in k},
        "timers": {k: v for k, v in snap["timers"].items()
                   if "generation" in k},
    }
    snap_path = os.environ.get(
        "PT_QUANTIZED_SERVING_BENCH_SNAPSHOT",
        os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                     "bench_quantized_serving_last.json"))
    regressions = []
    try:
        prev = stat_diff.load_snapshot(snap_path)
        regressions = stat_diff.find_regressions(
            stat_diff.diff_snapshots(prev, cur), threshold_pct=25.0)
        regressions = [r for r in regressions if r.startswith("timer")]
    except OSError:
        pass
    try:
        os.makedirs(os.path.dirname(snap_path), exist_ok=True)
        with open(snap_path, "w") as f:
            json.dump(cur, f)
    except OSError:
        pass

    return {
        "workload": "decoder L%d-H%d: %d greedy requests, %d new "
                    "tokens; int8 weights + int8 KV vs fp32" %
                    (cfg.layers, cfg.hidden, R, total_new),
        "logit_max_abs_delta": round(max_abs, 5),
        "logit_mse": round(mse, 7),
        "greedy_token_agreement": round(greedy_agree, 4),
        "error_budget_ok": max_abs < 0.25 and mse < 5e-3
        and greedy_agree >= 0.999,
        "pool_byte_budget": int(budget),
        "fp32_blocks_at_budget": int(nb_f32),
        "int8_blocks_at_budget": int(nb_i8),
        "fp32_capacity_seqs": int(f32_eng.kv_capacity_seqs()),
        "int8_capacity_seqs": int(q_eng.kv_capacity_seqs()),
        "capacity_ratio": round(cap_ratio, 2),
        "meets_2x_capacity": cap_ratio >= 2.0,
        "fp32_kv_bytes_per_seq": int(f32_eng.kv_bytes_per_seq()),
        "int8_kv_bytes_per_seq": int(q_eng.kv_bytes_per_seq()),
        "weight_bytes_saved": int(quant.weight_bytes_saved(qparams)),
        "fp32_tokens_per_sec": round(total_new / f32_best, 1),
        "int8_tokens_per_sec": round(total_new / q_best, 1),
        "greedy_streams_agree": "%d/%d" % (agree, R),
        "mean_agreed_prefix_tokens": round(mean_prefix, 1),
        "kv_quant_blocks_written": kvq_blocks,
        "steady_state_recompiles": recompiles,
        "mixed_step_p95_regressions": regressions,
    }


def _spmd_worker():
    """spmd block worker (ISSUE 6, docs/spmd.md): runs in a FRESH
    process (env: JAX_PLATFORMS=cpu + --xla_force_host_platform_
    device_count=8 set by _spawn_spmd before python starts) because the
    8 virtual devices must exist before jax initializes its backend —
    the main worker has already committed to the real one.

    Workload: a 12-layer BERT-shaped fused train step (forward +
    backward + adam) under three plans — single-device, dp4 (the
    data-parallel scaling claim), and dp4xmp2 with Megatron-style
    tensor-parallel rules (the parity claim: same seeds must give the
    same per-step losses as single-device to fp32 tolerance, with zero
    steady-state recompiles).

    HONESTY GATE: the >=1.5x dp4-vs-dp1 acceptance is physically
    impossible when the container has fewer host cores than mesh
    devices — 4 fake devices time-slice one core. The block reports the
    measured speedup as-is and sets core_limited=true LOUDLY instead of
    faking a pass (the round-2 lesson: never silently bench the wrong
    thing)."""
    import jax
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as pt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.mesh import ShardingPlan
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretraining_loss)

    assert jax.default_backend() == "cpu", jax.default_backend()
    assert len(jax.devices()) >= 8, len(jax.devices())
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1

    # dropout off: the parity claim needs a deterministic forward, and
    # the dropout key stream legitimately differs between the fused
    # (single-device) and unfused (mesh) attention traces — different
    # valid masks, not wrong math (docs/spmd.md, "Dropout under a mesh")
    cfg = BertConfig(vocab_size=512, hidden_size=128,
                     num_hidden_layers=12, num_attention_heads=4,
                     intermediate_size=256, max_position_embeddings=64,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    B, S, parity_steps, timed_steps = 8, 32, 3, 5
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    mlm = np.where(rng.rand(B, S) < 0.15, ids, -100).astype(np.int32)
    nsp = rng.randint(0, 2, (B, 1)).astype(np.int32)

    def mp_rules(name, shape):
        if len(shape) == 2:
            if ("linear1" in name or "q_proj" in name
                    or "k_proj" in name or "v_proj" in name):
                return P(None, "mp")
            if "linear2" in name or "out_proj" in name:
                return P("mp", None)
        return P()

    def run(plan):
        pt.dygraph.seed(0)
        np.random.seed(0)
        model = BertForPretraining(cfg)
        opt = pt.optimizer.Adam(1e-3, parameters=model.parameters())
        step = TrainStep(model, pretraining_loss, opt, plan=plan)
        losses = [float(step((ids,), (mlm, nsp)))
                  for _ in range(parity_steps)]
        cache0 = step._step_fn._cache_size()
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            loss = step((ids,), (mlm, nsp))
        float(loss)  # sync
        dt = time.perf_counter() - t0
        recompiles = step._step_fn._cache_size() - cache0
        return timed_steps / dt, losses, recompiles

    sps1, losses1, rc1 = run(None)
    sps4, _, rc4 = run(ShardingPlan("dp4"))
    spsmp, losses_mp, rcmp = run(
        ShardingPlan("dp4xmp2", params=mp_rules))

    speedup = sps4 / sps1
    max_diff = max(abs(a - b) for a, b in zip(losses1, losses_mp))
    parity_ok = max_diff < 5e-4  # fp32 tolerance over a 12-layer stack
    core_limited = cores < 8
    gate = speedup >= 1.5
    if not gate and core_limited:
        print("WARN: dp4 speedup %.2fx < 1.5x with only %d host "
              "core(s) backing 8 fake devices — core_limited, not a "
              "scaling regression (docs/spmd.md)" % (speedup, cores),
              file=sys.stderr)
    print(json.dumps({
        "workload": "BERT-shaped L%d-H%d fused train step (B=%d, S=%d, "
                    "fp32, adam) on 8 virtual CPU devices"
                    % (cfg.num_hidden_layers, cfg.hidden_size, B, S),
        "host_cores": cores,
        "dp1_steps_per_sec": round(sps1, 3),
        "dp4_steps_per_sec": round(sps4, 3),
        "dp4_speedup": round(speedup, 3),
        "dp4_speedup_gate_1p5x": bool(gate),
        "core_limited": bool(core_limited),
        "dp4xmp2_steps_per_sec": round(spsmp, 3),
        "dp4xmp2_loss_max_abs_diff": float(max_diff),
        "dp4xmp2_loss_parity_fp32": bool(parity_ok),
        "steady_state_recompiles": {"dp1": rc1, "dp4": rc4,
                                    "dp4xmp2": rcmp},
        "per_step_losses_dp1": [round(v, 6) for v in losses1],
        "per_step_losses_dp4xmp2": [round(v, 6) for v in losses_mp],
    }))


def _spawn_spmd(timeout=900, worker="--spmd-worker"):
    """Run a mesh-needing bench worker in a FRESH process that owns 8
    fake CPU devices (they must predate jax backend init). `worker` is
    the bench.py argv flag selecting the worker body."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    import re as _re
    flags = _re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                    env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), worker],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        out, err = _kill_group(proc)
    sys.stderr.write(err or "")
    for line in reversed((out or "").splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return None


def _quant_collectives_worker():
    """quantized_collectives block worker (ISSUE 17, docs/spmd.md
    "Quantized collectives"): int8 block-scaled gradient exchange vs
    the synchronous fp32 oracle in TrainStep, on a 12-layer BERT-shaped
    step under dp4 with grad_accum_steps=4. Fresh process for the same
    reason as _spmd_worker: 8 fake devices before backend init.

    Measures the three ISSUE-17 acceptance gates directly:
    - per-step dp sync bytes >= 3x smaller, from the build-time census
      manifest (the same numbers STAT_mesh_collective_bytes{axis,dtype}
      publishes per step);
    - int8 overlapped step time <= synchronous fp32 step time
      (interleaved timing rounds so host drift hits both equally);
    - loss trajectory within budget vs the fp32 oracle over 50 steps.
    Plus: zero steady-state recompiles per mode, and flag-off
    determinism (the legacy GSPMD path is untouched).

    The legacy (flag-off) step time is reported transparently: on
    shared-memory CPU fake devices XLA's native AllReduce is nearly
    free, so "int8 faster than legacy" is NOT claimed here — the claim
    is int8-deferred vs fp32-explicit at equal exchange structure,
    where the wire-byte ratio is what a real DCN/ICI fabric would
    amortize (docs/spmd.md spells out the CPU-vs-TPU caveat)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.flags import set_flags
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.mesh import ShardingPlan
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretraining_loss)

    assert jax.default_backend() == "cpu", jax.default_backend()
    assert len(jax.devices()) >= 8, len(jax.devices())

    cfg = BertConfig(vocab_size=512, hidden_size=128,
                     num_hidden_layers=12, num_attention_heads=4,
                     intermediate_size=256, max_position_embeddings=64,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    # B=16: divisible by dp4 x accum4 (the manual path splits the local
    # shard into k microbatches)
    B, S, accum, traj_steps = 16, 32, 4, 50
    rng = np.random.RandomState(0)
    batches = []
    for _ in range(traj_steps):
        ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
        mlm = np.where(rng.rand(B, S) < 0.15, ids, -100).astype(np.int32)
        nsp = rng.randint(0, 2, (B, 1)).astype(np.int32)
        batches.append((ids, mlm, nsp))

    def build(mode):
        pt.dygraph.seed(0)
        np.random.seed(0)
        set_flags({"FLAGS_collective_quant": mode})
        model = BertForPretraining(cfg)
        opt = pt.optimizer.Adam(1e-3, parameters=model.parameters())
        return TrainStep(model, pretraining_loss, opt,
                         plan=ShardingPlan("dp4"),
                         grad_accum_steps=accum)

    def trajectory(mode):
        step = build(mode)
        losses = [float(step((ids,), (mlm, nsp)))
                  for ids, mlm, nsp in batches]
        return step, losses

    step_off, losses_off = trajectory("off")
    _, losses_off2 = trajectory("off")
    step_fp32, losses_fp32 = trajectory("fp32")
    step_int8, losses_int8 = trajectory("int8")

    off_deterministic = losses_off == losses_off2
    loss_diff = max(abs(a - b)
                    for a, b in zip(losses_fp32, losses_int8))
    recompiles = {m: s._step_fn._cache_size() - 1
                  for m, s in (("off", step_off), ("fp32", step_fp32),
                               ("int8", step_int8))}

    # census: per-step dp exchange bytes from the build-time manifest
    # (fp32 counts k explicit syncs, int8 one deferred exchange)
    by_fp32 = dict(step_fp32._coll_manifest["bytes"])
    by_int8 = dict(step_int8._coll_manifest["bytes"])
    bytes_ratio = sum(by_fp32.values()) / max(1, sum(by_int8.values()))

    # timing: interleaved rounds so thermal/host drift hits both modes
    ids, mlm, nsp = batches[0]
    t_fp32 = t_int8 = t_off = 0.0
    rounds, per_round = 3, 5
    for s in (step_fp32, step_int8, step_off):  # warm
        float(s((ids,), (mlm, nsp)))
    for _ in range(rounds):
        for s, key in ((step_fp32, "fp32"), (step_int8, "int8"),
                       (step_off, "off")):
            t0 = time.perf_counter()
            for _ in range(per_round):
                loss = s((ids,), (mlm, nsp))
            float(loss)  # sync
            dt = time.perf_counter() - t0
            if key == "fp32":
                t_fp32 += dt
            elif key == "int8":
                t_int8 += dt
            else:
                t_off += dt
    n = rounds * per_round
    sps = {"fp32_sync": n / t_fp32, "int8_overlapped": n / t_int8,
           "off_legacy_gspmd": n / t_off}

    print(json.dumps({
        "workload": "BERT-shaped L%d-H%d train step, dp4, "
                    "grad_accum=%d (B=%d, S=%d, adam) on 8 virtual "
                    "CPU devices" % (cfg.num_hidden_layers,
                                     cfg.hidden_size, accum, B, S),
        "per_step_sync_bytes_fp32": by_fp32,
        "per_step_sync_bytes_int8": by_int8,
        "sync_bytes_ratio": round(bytes_ratio, 2),
        "sync_bytes_gate_3x": bool(bytes_ratio >= 3.0),
        "steps_per_sec": {k: round(v, 3) for k, v in sps.items()},
        "int8_not_slower_than_fp32_sync":
            bool(sps["int8_overlapped"] >= sps["fp32_sync"]),
        "loss_max_abs_diff_int8_vs_fp32_%dsteps" % traj_steps:
            float(loss_diff),
        "loss_budget_0p05": bool(loss_diff < 0.05),
        "off_mode_deterministic": bool(off_deterministic),
        "steady_state_recompiles": recompiles,
        "quantized_buckets_per_exchange":
            step_int8._coll_manifest["buckets"],
        "per_step_losses_fp32_first5":
            [round(v, 6) for v in losses_fp32[:5]],
        "per_step_losses_int8_first5":
            [round(v, 6) for v in losses_int8[:5]],
    }))


def bench_quantized_collectives():
    """quantized_collectives block (ISSUE 17): int8 block-scaled
    gradient AllReduce vs the synchronous fp32 oracle under dp4;
    subprocess-isolated for the 8 fake devices (see
    _quant_collectives_worker)."""
    rec = _spawn_spmd(worker="--quant-collectives-worker")
    return rec if rec is not None else {
        "error": "quant collectives worker produced no result "
                 "(see stderr)"}


def _mp_quant_collectives_worker():
    """mp_quantized_collectives block worker (ISSUE 19, docs/spmd.md
    "Quantized collectives on the mp axis"): the SAME 12-layer
    BERT-shaped step as _quant_collectives_worker, but under dp4xmp2
    with Megatron param rules — FFN up column-sharded, FFN down and the
    embedding table row-sharded over mp — so the mp-axis quantized
    all-gather composes with the dp-axis gradient wire in one build.

    Measures the ISSUE-19 acceptance gates directly:
    - ZERO demotions: every mesh-sharded param rides the quantized
      gather (STAT_collective_quant_demotions delta across all composed
      builds must be 0, and no demotion warning fires);
    - per-step mp-axis sync bytes >= 3x smaller for int8 vs the
      fp32-composed oracle, from the per-axis census manifest (the same
      numbers STAT_mesh_collective_bytes{axis="mp",dtype} publishes);
    - 50-step loss trajectory within 0.05 of the fp32-composed oracle
      (which itself must match the legacy flag-off GSPMD path — the
      gather/slice math is exact in fp32);
    - zero steady-state recompiles per mode (the out_shardings pin:
      sharded state stays sharded at rest without a spec-spelling
      cache miss);
    - fp8-e4m3 exercised where quant.supports_fp8() admits, with the
      resolved wire mode pinned in the artifact either way.

    Step-time numbers carry the same CPU-fabric caveat as the dp block:
    on shared-memory fake devices XLA's AllGather is nearly free, so
    no speed CLAIM is made — the wire-byte ratio is what a real
    DCN/ICI fabric would amortize."""
    import warnings
    import jax
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as pt
    from paddle_tpu import monitor, quant
    from paddle_tpu.flags import set_flags
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.mesh import ShardingPlan
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretraining_loss)

    assert jax.default_backend() == "cpu", jax.default_backend()
    assert len(jax.devices()) >= 8, len(jax.devices())

    cfg = BertConfig(vocab_size=512, hidden_size=128,
                     num_hidden_layers=12, num_attention_heads=4,
                     intermediate_size=256, max_position_embeddings=64,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    H, I, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size

    def rules(name, shape):
        # Megatron layout (examples/bert_pretrain.py): FFN up
        # column-sharded, FFN down row-sharded, embedding row-sharded
        if shape == (H, I):
            return P(None, "mp")
        if shape == (I, H):
            return P("mp", None)
        if shape == (V, H):
            return P("mp", None)
        return P()

    B, S, accum, traj_steps = 16, 32, 4, 50
    rng = np.random.RandomState(0)
    batches = []
    for _ in range(traj_steps):
        ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
        mlm = np.where(rng.rand(B, S) < 0.15, ids, -100).astype(np.int32)
        nsp = rng.randint(0, 2, (B, 1)).astype(np.int32)
        batches.append((ids, mlm, nsp))

    def build(mode, mp):
        pt.dygraph.seed(0)
        np.random.seed(0)
        set_flags({"FLAGS_collective_quant": mode,
                   "FLAGS_collective_quant_mp": mp})
        model = BertForPretraining(cfg)
        # 1e-4 (vs the dp block's 1e-3): quantizing BOTH wires (dp
        # grads + mp gathers) doubles the rounding noise sources, and
        # at 1e-3 Adam chaotically amplifies even the fp32-composed-
        # vs-legacy reduction-order difference to ~8e-3 by step 50 —
        # the budget gates quantization error, not trajectory chaos
        opt = pt.optimizer.Adam(1e-4, parameters=model.parameters())
        return TrainStep(model, pretraining_loss, opt,
                         plan=ShardingPlan("dp4xmp2", params=rules),
                         grad_accum_steps=accum)

    def trajectory(mode, mp):
        d0 = monitor.get_float_stats().get(
            "STAT_collective_quant_demotions", 0.0)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            step = build(mode, mp)
            losses = [float(step((ids,), (mlm, nsp)))
                      for ids, mlm, nsp in batches]
        d1 = monitor.get_float_stats().get(
            "STAT_collective_quant_demotions", 0.0)
        warned = any("legacy GSPMD" in str(w.message) for w in caught)
        return step, losses, int(d1 - d0), warned

    fp8_admitted = quant.supports_fp8()
    step_off, losses_off, _, _ = trajectory("off", "off")
    step_fp32, losses_fp32, dem_fp32, warn_fp32 = trajectory(
        "fp32", "fp32")
    step_int8, losses_int8, dem_int8, warn_int8 = trajectory(
        "int8", "int8")
    step_fp8, losses_fp8, dem_fp8, warn_fp8 = trajectory("int8", "fp8")

    oracle_diff = max(abs(a - b)
                      for a, b in zip(losses_off, losses_fp32))
    loss_diff = max(abs(a - b)
                    for a, b in zip(losses_fp32, losses_int8))
    loss_diff_fp8 = max(abs(a - b)
                        for a, b in zip(losses_fp32, losses_fp8))
    recompiles = {m: s._step_fn._cache_size() - 1
                  for m, s in (("off", step_off), ("fp32", step_fp32),
                               ("int8", step_int8), ("fp8", step_fp8))}

    # census: per-step mp-axis gather bytes from the per-axis manifest
    def _mp_bytes(step):
        axes = step._coll_manifest.get("axes", {})
        return dict(axes.get("mp", {}).get("bytes", {}))

    mp_fp32, mp_int8, mp_fp8 = (_mp_bytes(s) for s in
                                (step_fp32, step_int8, step_fp8))
    mp_ratio = sum(mp_fp32.values()) / max(1, sum(mp_int8.values()))

    # timing: interleaved rounds (CPU caveat above — reported, not
    # claimed)
    ids, mlm, nsp = batches[0]
    t = {"off": 0.0, "fp32": 0.0, "int8": 0.0}
    rounds, per_round = 3, 5
    steps = {"off": step_off, "fp32": step_fp32, "int8": step_int8}
    for s in steps.values():  # warm
        float(s((ids,), (mlm, nsp)))
    for _ in range(rounds):
        for key, s in steps.items():
            t0 = time.perf_counter()
            for _ in range(per_round):
                loss = s((ids,), (mlm, nsp))
            float(loss)  # sync
            t[key] += time.perf_counter() - t0
    n = rounds * per_round
    sps = {"off_legacy_gspmd": n / t["off"],
           "fp32_composed": n / t["fp32"],
           "int8_composed": n / t["int8"]}

    gathers = int(monitor.get_float_stats().get(
        "STAT_collective_quant_mp_gathers", 0.0))
    print(json.dumps({
        "workload": "BERT-shaped L%d-H%d train step, dp4xmp2 Megatron "
                    "rules (FFN up col / FFN down row / embedding row "
                    "over mp), grad_accum=%d (B=%d, S=%d, adam) on 8 "
                    "virtual CPU devices" % (cfg.num_hidden_layers,
                                             cfg.hidden_size, accum,
                                             B, S),
        "mp_gather_params": len(step_int8._coll_plan.gathers),
        "demotions": {"fp32": dem_fp32, "int8": dem_int8,
                      "fp8": dem_fp8},
        "demotion_warning_fired": bool(warn_fp32 or warn_int8
                                       or warn_fp8),
        "zero_demotions_gate": bool(
            dem_fp32 == dem_int8 == dem_fp8 == 0),
        "per_step_mp_sync_bytes_fp32": mp_fp32,
        "per_step_mp_sync_bytes_int8": mp_int8,
        "per_step_mp_sync_bytes_fp8": mp_fp8,
        "mp_sync_bytes_ratio": round(mp_ratio, 2),
        "mp_sync_bytes_gate_3x": bool(mp_ratio >= 3.0),
        "loss_max_abs_diff_fp32_vs_legacy_%dsteps" % traj_steps:
            float(oracle_diff),
        "loss_max_abs_diff_int8_vs_fp32_%dsteps" % traj_steps:
            float(loss_diff),
        "loss_max_abs_diff_fp8_vs_fp32_%dsteps" % traj_steps:
            float(loss_diff_fp8),
        "loss_budget_0p05": bool(loss_diff < 0.05
                                 and loss_diff_fp8 < 0.05),
        "steady_state_recompiles": recompiles,
        "recompile_note": "the legacy flag-off path recompiles once "
                          "on mp-sharded state (GSPMD respells "
                          "P('mp', None) as P('mp',) after step 0 — "
                          "an equal-meaning, unequal-cache-key spec); "
                          "the composed modes pin out_shardings and "
                          "stay at zero",
        "fp8_probe_admitted": bool(fp8_admitted),
        "fp8_resolved_wire_mode": step_fp8._coll_plan.mp_mode,
        "mp_gather_exchanges_observed": gathers,
        "steps_per_sec": {k: round(v, 3) for k, v in sps.items()},
        "timing_caveat": "shared-memory CPU fake devices — wire-byte "
                         "ratio is the claim, step time is not",
        "per_step_losses_fp32_first5":
            [round(v, 6) for v in losses_fp32[:5]],
        "per_step_losses_int8_first5":
            [round(v, 6) for v in losses_int8[:5]],
    }))


def _mp_quant_gang_ab():
    """Live 2-process gang A/B for the composed quantized wire
    (ISSUE 19): the PR-13 launcher forms a REAL jax gang (2 localhost
    processes x 2 fake CPU devices = dp2xmp2) over the Megatron-ruled
    MLP in tests/gang_runner.py, once with the quantized wire off and
    once with GANG_QUANT=int8 + GANG_QUANT_MP=int8. Per-rank evidence
    comes off the heartbeat-digest plane, not the worker's stdout:

    - GAUGE_gang_collective_wait_frac{rank} — fraction of in-step time
      in the exchange+sync tail, per rank, from the supervisor's
      straggler scorer;
    - TIMER_gang_step_phase_us{rank,phase="exchange"} p50/p95 — the
      digest-carried exchange-phase timer, re-emitted rank-labeled;
    - bytes-by-dtype census: summing each rank's digest ``coll``
      deltas (digests_rank<k>.jsonl under the supervisor log_dir)
      over the steps they span gives per-step wire bytes per dtype —
      int8 payloads + fp32 scale rows must appear in the quantized
      run and be absent from the off run.

    CPU-fabric caveat: localhost shared-memory collectives make
    wait_frac/exchange-time DELTAS noise-bound — the A/B documents
    that the quantized wire runs on a live gang with the dtype census
    to prove it, not a speedup claim."""
    import glob
    import shutil
    import tempfile
    from paddle_tpu import monitor
    from paddle_tpu.launch import GangSupervisor
    from paddle_tpu.monitor import labeled

    repo = os.path.dirname(os.path.abspath(__file__))
    runner = os.path.join(repo, "tests", "gang_runner.py")
    tmp = tempfile.mkdtemp(prefix="pt_mpquant_bench_")
    STEPS = 120

    def _run(name, quant_env):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env.update({"GANG_STEPS": str(STEPS), "GANG_PHASES": "1",
                    "GANG_PLAN": "dp2xmp2"})
        env.update(quant_env)
        sup = GangSupervisor(
            [runner], 2, cpu_devices_per_proc=2,
            log_dir=os.path.join(tmp, name), env=env,
            heartbeat_interval_s=0.05, heartbeat_timeout_s=30.0,
            spawn_grace_s=300.0, max_restarts=0,
            name="bench_mpq_" + name)
        sup.start()
        fracs: dict = {}
        try:
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline:
                st = sup.status()
                for w in st["workers"]:
                    if w.get("wait_frac") is not None:
                        fracs[w["rank"]] = w["wait_frac"]
                done = max((w["step"] for w in st["workers"]),
                           default=0) >= STEPS
                dead = all(w["state"] in ("exited", "died", "lost")
                           for w in st["workers"])
                if done or dead:
                    break
                time.sleep(0.05)
        finally:
            sup.stop()
        # exchange-phase p50/p95 per rank off the supervisor's
        # rank-labeled re-emission of the digest timers
        phases = {}
        for rank in (0, 1):
            key = labeled("TIMER_gang_step_phase_us",
                          {"gang": "bench_mpq_" + name,
                           "rank": str(rank), "phase": "exchange"})
            ts = monitor.timer_get(key)
            if ts["count"]:
                phases[str(rank)] = {"p50_us": round(ts["p50"], 1),
                                     "p95_us": round(ts["p95"], 1)}
        # bytes-by-dtype census from the digest JSONL logs: sum each
        # rank's coll deltas, divide by the steps they cover
        sys.path.insert(0, os.path.join(repo, "tools"))
        try:
            from trace_merge import load_digests
        finally:
            sys.path.pop(0)
        census = {}
        for path in sorted(glob.glob(os.path.join(
                tmp, name, "digests_rank*.jsonl"))):
            rank = path.rsplit("digests_rank", 1)[1].split(".")[0]
            digs = load_digests(path)
            agg: dict = {}
            hi = 0
            for d in digs:
                hi = max(hi, int(d.get("step", 0) or 0))
                for dt, nb in (d.get("coll") or {}).items():
                    agg[dt] = agg.get(dt, 0) + int(nb)
            if hi:
                census[rank] = {dt: int(round(nb / hi))
                                for dt, nb in agg.items()}
        return {"per_rank_wait_frac": {str(k): v
                                       for k, v in sorted(fracs.items())},
                "exchange_phase_us": phases,
                "per_step_wire_bytes_by_dtype": census}

    try:
        off = _run("off", {})
        on = _run("int8", {"GANG_QUANT": "int8",
                           "GANG_QUANT_MP": "int8"})
        on_dts = set()
        for per in on["per_step_wire_bytes_by_dtype"].values():
            on_dts |= set(per)
        return {
            "workload": "2-process gang x 2 CPU devices = dp2xmp2, "
                        "Megatron MLP, %d steps, 50ms heartbeats, "
                        "phase timers on" % STEPS,
            "quant_off": off,
            "quant_int8_mp_int8": on,
            "int8_on_wire": bool("int8" in on_dts),
            "fabric_caveat": "localhost shared-memory collectives; "
                             "the dtype census is the evidence, the "
                             "wait/exchange deltas are noise-bound",
        }
    except Exception as e:  # noqa: BLE001 - artifact records the failure
        return {"error": "%s: %s" % (type(e).__name__, e)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_mp_quant_collectives():
    """mp_quantized_collectives block (ISSUE 19): mp-axis quantized
    all-gather composed with Megatron sharding plans — dp4xmp2 BERT
    gates in a subprocess (8 fake devices must predate backend init,
    see _mp_quant_collectives_worker) plus a live 2-process gang A/B
    reading the per-rank digest plane."""
    rec = _spawn_spmd(worker="--mp-quant-collectives-worker")
    out = rec if rec is not None else {
        "error": "mp quant collectives worker produced no result "
                 "(see stderr)"}
    out["gang_ab"] = _mp_quant_gang_ab()
    return out


def bench_autotune():
    """adaptive kernel dispatch block (ISSUE 16, docs/autotune.md):
    the auto-tuned ragged-step geometry vs (a) the WORST candidate the
    tuner verified eligible and (b) the hand-set flag defaults, over a
    prompt-heavy request stream (the regime where chunk geometry
    dominates: ~70-token prompts stream through the mixed step a chunk
    at a time, so a 4x larger chunk cuts prefill step count ~4x).

    Gates (ISSUE 16 acceptance): tuned >= 1.15x generated tokens/s vs
    the worst eligible candidate AND >= 1.0x vs the defaults; streams
    bitwise-identical across all three forms keyed by request_id; zero
    steady-state recompiles after the tuning phase, INCLUDING across a
    simulated process restart that reloads the persisted policy (zero
    new trials, zero trace-cache misses, identical streams). Passes
    interleave tuned/defaults/worst per round with best-of-N per
    engine — same honest-margin methodology as the PR-10 mixed block."""
    import tempfile
    from dataclasses import replace
    from paddle_tpu import autotune
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest,
                                       SamplingParams, init_params)
    from paddle_tpu.monitor import stat_get

    cfg = DecoderConfig(vocab_size=128, hidden=64, layers=2, heads=4,
                        max_seq_len=128)
    params = init_params(cfg, seed=0)
    cache = tempfile.mkdtemp(prefix="pt_autotune_bench_")

    # One request set PER PASS: re-draining identical prompts would
    # hit the engines' prefix caches from pass 2 on and measure the
    # cache-hit regime, where prefill geometry is irrelevant — real
    # serving sees distinct prompts, and distinct prompts are what the
    # tuner's probe optimizes for
    R, PASSES = 24, 4

    def mkreqs(seed):
        rng = np.random.RandomState(seed)
        return [GenerationRequest(
            prompt=list(rng.randint(1, cfg.vocab_size,
                                    size=int(rng.randint(60, 91)))),
            max_new_tokens=int(rng.randint(4, 9)),
            sampling=SamplingParams(
                temperature=0.8 if i % 2 else 0.0,
                top_k=16 if i % 3 == 0 else 0, seed=i),
            request_id=i) for i in range(R)]

    pass_reqs = [mkreqs(11 + p) for p in range(PASSES)]

    mk = lambda **kw: GenerationEngine(  # noqa: E731
        cfg, params, num_blocks=256, decode_width=8,
        program_cache_dir=cache, **kw)

    # --- tuning phase: one resolve searches the geometry space ------
    autotune.reset()
    t_tune0 = stat_get("STAT_autotune_trials")
    tuned_eng = mk(autotune=True)
    entry = tuned_eng._policy_entry
    if entry is None:
        return {"error": "tuning did not complete (reference trial "
                         "failed)"}
    eligible = [c for c in entry["candidates"]
                if c.get("eligible") and "us_per_token" in c]
    worst = max(eligible, key=lambda c: c["us_per_token"])
    defaults = entry["candidates"][0]  # reference form == flag defaults
    # when the tuner confirms the hand-set defaults ARE the optimum
    # (common on CPU, where chunk sizes >= the decode width plateau),
    # tuned and defaults are the SAME form — one engine serves both
    # roles and the ratio is 1.0 by identity, not a noise coin-flip
    # measured between two copies of the same executable
    tuned_is_defaults = entry["label"] == defaults["label"]

    def pinned(c):
        return mk(autotune=False, kernel=c["kernel"],
                  block_size=c["block_size"],
                  prefill_chunk=c["prefill_chunk"],
                  token_budget=c["token_budget"])

    defaults_eng = tuned_eng if tuned_is_defaults else pinned(defaults)
    worst_eng = pinned(worst)
    for e in (tuned_eng, defaults_eng, worst_eng):
        e.warmup()

    def run_pass(eng, reqs):
        for r in reqs:
            eng.submit(replace(r))
        done = []
        t0 = time.perf_counter()
        while not eng.idle:
            done.extend(eng.step())
        wall = time.perf_counter() - t0
        return wall, {res.request_id: tuple(res.tokens)
                      for res in done}

    # interleaved best-of-N: every engine samples every drift window;
    # throughput per pass uses that pass's own token count, best-of
    # over passes per engine
    pass_new = [sum(r.max_new_tokens for r in rs) for rs in pass_reqs]
    c0 = stat_get("STAT_generation_compile")
    best_tps = {}
    streams = {}  # name -> list of per-pass {request_id: tokens}
    for p in range(PASSES):
        for name, eng in (("tuned", tuned_eng),
                          ("defaults", defaults_eng),
                          ("worst_eligible", worst_eng)):
            wall, st = run_pass(eng, pass_reqs[p])
            t = pass_new[p] / wall
            if t > best_tps.get(name, 0.0):
                best_tps[name] = t
            streams.setdefault(name, []).append(st)
    recompiles = int(stat_get("STAT_generation_compile") - c0)
    bitwise = (streams["tuned"] == streams["defaults"]
               == streams["worst_eligible"])
    tps = {n: round(t, 1) for n, t in best_tps.items()}

    # --- restart: reload the persisted policy, recompile nothing ----
    autotune.reset()
    t0 = stat_get("STAT_autotune_trials")
    m0 = stat_get("STAT_program_cache_trace_miss")
    r_eng = mk(autotune=True)
    r_eng.warmup()
    _, r_streams = run_pass(r_eng, pass_reqs[0])
    restart = {
        "policy_source": (r_eng._policy_entry or {}).get("source"),
        "retune_trials": int(stat_get("STAT_autotune_trials") - t0),
        "trace_cache_misses": int(
            stat_get("STAT_program_cache_trace_miss") - m0),
        "streams_bitwise_identical": r_streams == streams["tuned"][0],
    }

    vs_worst = round(tps["tuned"] / tps["worst_eligible"], 2)
    vs_defaults = 1.0 if tuned_is_defaults \
        else round(tps["tuned"] / tps["defaults"], 2)
    return {
        "workload": "decoder L%d-H%d: %d fresh requests/pass x %d "
                    "passes, prompts 60..90, ~%d new tokens/pass, "
                    "width 8" % (cfg.layers, cfg.hidden, R, PASSES,
                                 pass_new[0]),
        "tuning": {"winner": entry["label"],
                   "trials": int(stat_get("STAT_autotune_trials")
                                 - t_tune0),
                   "tuned_s": entry["tuned_s"],
                   "candidates": entry["candidates"]},
        "tokens_per_sec": tps,
        "speedup_tuned_vs_worst_eligible": vs_worst,
        "speedup_tuned_vs_defaults": vs_defaults,
        "tuned_is_defaults_form": bool(tuned_is_defaults),
        "meets_1p15x_vs_worst": vs_worst >= 1.15,
        "meets_1p0x_vs_defaults": vs_defaults >= 1.0,
        "tokens_bitwise_identical": bool(bitwise),
        "steady_state_recompiles": recompiles,
        "restart": restart,
    }


def bench_spmd():
    """spmd block (ISSUE 6): dp/mp scaling + loss parity of the
    mesh-native runtime, measured in a subprocess that owns the 8 fake
    CPU devices (see _spmd_worker)."""
    rec = _spawn_spmd()
    return rec if rec is not None else {
        "error": "spmd worker produced no result (see stderr)"}


def bench_chaos():
    """chaos block (ISSUE 9, docs/robustness.md): the fault-injection +
    self-healing story, measured three ways —

    - the disarmed failpoint hook itself (ns/call): the hot-path
      contract is ONE dict lookup, same shape as tracing-off;
    - steady-state pooled throughput A/B: failpoints fully disarmed vs
      armed on an unrelated site (checkpoint.save, which serving never
      reaches) — the delta must be noise, proving arming elsewhere
      costs the serving path nothing;
    - a fault storm against a live PredictorPool: serving.execute
      raises on every call until two consecutive batches die, the
      supervisor restarts the worker, and the block measures recovery
      latency (disarm -> first healthy response), restart count, and a
      deadline-shed probe (deadline=0 submit rejected at admit).
    """
    import shutil
    import tempfile
    import paddle_tpu as pt
    from paddle_tpu import failpoints, serving
    from paddle_tpu.monitor import stat_get

    # --- disarmed hook microbench ------------------------------------
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        failpoints.failpoint("bench.disarmed")
    ns_per_call = (time.perf_counter() - t0) / n * 1e9

    R, H_IN = 120, 32
    model_dir = tempfile.mkdtemp(prefix="pt_chaos_bench_")
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", [H_IN])
            h = x
            for _ in range(8):
                h = pt.layers.fc(h, 64, act="relu")
            y = pt.layers.fc(h, 8)
        exe = pt.Executor()
        exe.run(startup)
        pt.io.save_inference_model(model_dir, ["x"], [y], exe,
                                   main_program=main)
        cfg = pt.inference.Config(model_dir)
        cfg.switch_shape_bucketing(True, buckets="pow2:32")

        rng = np.random.RandomState(0)
        reqs = [rng.rand(int(b), H_IN).astype(np.float32)
                for b in rng.randint(1, 9, size=R)]

        with serving.PredictorPool(pt.inference.create_predictor(cfg),
                                   max_batch=16) as pool:
            pool.warmup([np.zeros((1, H_IN), np.float32)])

            def stream():
                t0 = time.perf_counter()
                for r in reqs:
                    pool.run([r])
                return R / (time.perf_counter() - t0)

            # interleaved best-of A/B (the PR 7 scrape-cost
            # methodology): scheduler jitter dwarfs a zero-cost delta
            disarmed_runs, armed_runs = [], []
            failpoints.disarm("all")
            try:
                for _ in range(3):
                    disarmed_runs.append(stream())
                    with failpoints.armed("checkpoint.save=raise"):
                        armed_runs.append(stream())
            finally:
                failpoints.disarm("all")
            off_rps, on_rps = max(disarmed_runs), max(armed_runs)

            # --- fault storm + recovery -------------------------------
            restarts0 = stat_get("STAT_serving_restarts")
            shed0 = stat_get("STAT_serving_shed_at_admit")
            failpoints.arm_spec("serving.execute=raise")
            faults = 0
            for r in reqs[:2]:  # two dead batches -> worker crash
                try:
                    pool.run([r])
                except Exception:
                    faults += 1
            failpoints.disarm("serving.execute")
            t0 = time.perf_counter()
            recovered = False
            while time.perf_counter() - t0 < 30.0:
                try:
                    pool.run([reqs[0]], timeout=2.0)
                    recovered = True
                    break
                except Exception:
                    time.sleep(0.01)
            recovery_ms = (time.perf_counter() - t0) * 1e3

            # deadline-shed probe: a zero-budget submit must be shed
            # at admit, never dispatched
            shed_typed = False
            try:
                pool.submit([reqs[0]], deadline=0.0).result(timeout=5.0)
            except serving.DeadlineBurned:
                shed_typed = True
            except Exception:
                pass
            restarts = int(stat_get("STAT_serving_restarts") - restarts0)
            shed = int(stat_get("STAT_serving_shed_at_admit") - shed0)
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)

    return {
        "workload": "fc9-H64 pooled inference (in=%d), %d requests, "
                    "serving.execute fault storm" % (H_IN, R),
        "disarmed_hook_ns_per_call": round(ns_per_call, 1),
        "steady_state": {
            "disarmed_rows_per_sec": round(off_rps, 1),
            "armed_unrelated_rows_per_sec": round(on_rps, 1),
            # the contract: arming a site the path never reaches is
            # free; the residual is run-to-run noise, not hook cost
            "delta_pct": round((1.0 - on_rps / off_rps) * 100.0, 2),
        },
        "fault_storm": {
            "injected_faults_surfaced": faults,
            "worker_restarts": restarts,
            "recovered": recovered,
            "recovery_ms": round(recovery_ms, 1),
            "shed_at_admit": shed,
            "shed_typed_deadline_burned": shed_typed,
        },
    }


def bench_chaos_multihost():
    """chaos_multihost block (ISSUE 13, docs/robustness.md "Multi-host
    fault model"): a REAL 2-process jax gang (tests/gang_runner.py
    under paddle_tpu.launch.GangSupervisor, localhost processes
    standing in for hosts) trains 8 steps with auto-checkpointing;
    rank 1 is SIGKILLed mid-step. Measures the two recovery numbers
    the fault model promises —

    - detection_ms: SIGKILL -> the supervisor's worker_death event
      (process-poll path; the missed-heartbeat window bounds the hang
      path at heartbeat_timeout_s);
    - recovery_ms: SIGKILL -> first RESUMED training step of the
      restarted gang (step_progress event);

    and asserts the acceptance pin: the spliced loss stream of the
    killed run is bitwise-identical to an uninterrupted gang's.
    """
    import shutil
    import signal
    import tempfile
    from paddle_tpu.launch import GangSupervisor

    repo = os.path.dirname(os.path.abspath(__file__))
    runner = os.path.join(repo, "tests", "gang_runner.py")
    tmp = tempfile.mkdtemp(prefix="pt_gang_bench_")

    def _gang(name):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["GANG_STEPS"] = "8"
        env["GANG_CK_EVERY"] = "2"
        env["GANG_CKDIR"] = os.path.join(tmp, "ck_" + name)
        return GangSupervisor(
            [runner], 2, cpu_devices_per_proc=1,
            log_dir=os.path.join(tmp, name), env=env,
            heartbeat_interval_s=0.2, heartbeat_timeout_s=30.0,
            spawn_grace_s=300.0, max_restarts=2,
            restart_backoff_ms=50.0, name="bench_" + name)

    def _losses(logd):
        out = {}
        for fn in sorted(os.listdir(logd)):
            with open(os.path.join(logd, fn)) as f:
                for line in f:
                    parts = line.split()
                    if len(parts) == 3 and parts[0] == "STEP":
                        out[int(parts[1])] = parts[2]
        return out

    try:
        ref_sup = _gang("ref")
        ref_sup.run(timeout=600)
        ref = _losses(os.path.join(tmp, "ref"))

        sup = _gang("chaos")
        sup.start()
        try:
            t_kill = None
            deadline = time.monotonic() + 480
            while time.monotonic() < deadline:
                st = sup.status()
                if st["attempt"] == 0 and \
                        max(w["step"] for w in st["workers"]) >= 3:
                    w1 = [w for w in st["workers"] if w["rank"] == 1][0]
                    t_kill = time.monotonic()
                    os.kill(w1["pid"], signal.SIGKILL)
                    break
                time.sleep(0.02)
            if t_kill is None:
                return {"error": "gang never reached step 3: %s" % st}
            sup.wait(timeout=600)
        finally:
            sup.stop()
        got = _losses(os.path.join(tmp, "chaos"))
        ev = sup.events()
        det = [e for e in ev if e["t_mono"] >= t_kill
               and e["kind"] in ("worker_death", "worker_lost")]
        resumed = [e for e in ev if e["t_mono"] >= t_kill
                   and e["kind"] == "step_progress"]
        return {
            "workload": "2-process jax gang, dp=2, 8 steps, "
                        "checkpoint every 2, SIGKILL rank 1 mid-step",
            "detection_path": det[0]["kind"] if det else None,
            "detection_ms": round((det[0]["t_mono"] - t_kill) * 1e3, 1)
            if det else None,
            "recovery_ms": round((resumed[0]["t_mono"] - t_kill) * 1e3, 1)
            if resumed else None,
            "heartbeat_window_s": sup.heartbeat_timeout_s,
            "restarts": sup.status()["restarts"],
            "steps_completed": len(got),
            "resume_bitwise_identical":
                sorted(got) == sorted(ref) == list(range(1, 9))
                and got == ref,
        }
    except Exception as e:  # noqa: BLE001 - artifact records the failure
        return {"error": "%s: %s" % (type(e).__name__, e)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_slo():
    """slo block (ISSUE 12, docs/observability.md): the windowed-SLO
    engine measured three ways —

    - the disabled paths (ns/call): slo.evaluate() with FLAGS_slo off
      is ONE dict lookup (the tracing/failpoints contract), and
      stat_add with windows off vs on bounds the per-write cost of
      windowed aggregation;
    - enabled overhead A/B on pooled serving: same tenant-attributed
      request stream with the SLO engine off vs on (windows + labeled
      per-tenant series + objective evaluation per scrape), interleaved
      best-of like the chaos block;
    - a burn-rate storm against a live /sloz: serving.execute delayed
      past a tight request deadline via failpoint, every request
      misses, the fast burn-rate alert must TRIP on a real HTTP scrape;
      after disarm + healthy traffic it must CLEAR — the full SRE
      multi-window cycle observed end-to-end over HTTP.
    """
    import shutil
    import tempfile
    import urllib.request
    import paddle_tpu as pt
    from paddle_tpu import failpoints, introspect, monitor, serving, slo
    from paddle_tpu.flags import set_flags

    # --- disabled-path microbenches ----------------------------------
    set_flags({"FLAGS_slo": False})
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        slo.evaluate()
    eval_off_ns = (time.perf_counter() - t0) / n * 1e9

    monitor.disable_windows()
    t0 = time.perf_counter()
    for _ in range(n):
        monitor.stat_add("STAT_bench_slo_probe")
    stat_off_ns = (time.perf_counter() - t0) / n * 1e9
    monitor.enable_windows(bucket_s=10.0, n_buckets=360)
    t0 = time.perf_counter()
    for _ in range(n):
        monitor.stat_add("STAT_bench_slo_probe")
    stat_on_ns = (time.perf_counter() - t0) / n * 1e9
    monitor.disable_windows()

    R, H_IN = 120, 32
    model_dir = tempfile.mkdtemp(prefix="pt_slo_bench_")
    out: dict = {
        "disabled_evaluate_ns_per_call": round(eval_off_ns, 1),
        "stat_add_ns_windows_off": round(stat_off_ns, 1),
        "stat_add_ns_windows_on": round(stat_on_ns, 1),
        "stat_add_window_delta_ns": round(stat_on_ns - stat_off_ns, 1),
    }
    try:
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", [H_IN])
            h = x
            for _ in range(8):
                h = pt.layers.fc(h, 64, act="relu")
            y = pt.layers.fc(h, 8)
        exe = pt.Executor()
        exe.run(startup)
        pt.io.save_inference_model(model_dir, ["x"], [y], exe,
                                   main_program=main)
        cfg = pt.inference.Config(model_dir)
        cfg.switch_shape_bucketing(True, buckets="pow2:32")

        rng = np.random.RandomState(0)
        reqs = [rng.rand(int(b), H_IN).astype(np.float32)
                for b in rng.randint(1, 9, size=R)]

        with serving.PredictorPool(pt.inference.create_predictor(cfg),
                                   max_batch=16) as pool:
            pool.warmup([np.zeros((1, H_IN), np.float32)])

            def stream():
                t0 = time.perf_counter()
                for r in reqs:
                    pool.run([r], tenant="acme")
                return R / (time.perf_counter() - t0)

            # --- enabled overhead A/B (interleaved best-of) ----------
            off_runs, on_runs = [], []
            for _ in range(3):
                slo.disable()
                off_runs.append(stream())
                slo.enable(bucket_s=0.25, n_buckets=480)
                on_runs.append(stream())
                slo.evaluate()  # the per-scrape evaluation cost too
            slo.disable()
            off_rps, on_rps = max(off_runs), max(on_runs)
            out["steady_state"] = {
                "workload": "fc9-H64 pooled inference (in=%d), %d "
                            "tenant-attributed requests" % (H_IN, R),
                "slo_off_rows_per_sec": round(off_rps, 1),
                "slo_on_rows_per_sec": round(on_rps, 1),
                "overhead_pct": round(
                    (1.0 - on_rps / off_rps) * 100.0, 2),
                "overhead_us_per_request": round(
                    (1.0 / on_rps - 1.0 / off_rps) * 1e6, 2),
            }

            # --- burn-rate storm: trip and clear over live /sloz -----
            slo.enable(bucket_s=0.25, n_buckets=480)
            slo.clear_objectives()
            slo.register(slo.Objective(
                name="bench_deadline_miss", kind="ratio", target=0.95,
                bad="STAT_serving_deadline_missed",
                total="STAT_serving_requests",
                window_s=8.0, fast_window_s=2.0, slow_window_s=8.0,
                fast_burn=2.0, slow_burn=3.0,
                description="bench: <5% deadline misses"))
            srv = introspect.start(port=0)

            def scrape():
                return json.load(urllib.request.urlopen(
                    srv.url + "/sloz?format=json", timeout=10))

            def obj(z):
                return next(o for o in z["objectives"]
                            if o["name"] == "bench_deadline_miss")

            tripped = cleared = False
            trip_s = clear_s = None
            try:
                # every request now takes >= 20ms against a 4ms
                # deadline: a 100% miss storm
                failpoints.arm_spec("serving.execute=delay(20)")
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < 20.0:
                    pool.run([reqs[0]], deadline=0.004, tenant="acme")
                    z = scrape()
                    if obj(z)["alert"]["firing"]:
                        tripped = True
                        trip_s = time.perf_counter() - t0
                        break
                storm_obj = obj(z)
                failpoints.disarm("all")
                # healthy traffic until the short window recovers
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < 20.0:
                    pool.run([reqs[0]], deadline=30.0, tenant="bench")
                    z = scrape()
                    if not obj(z)["alert"]["firing"]:
                        cleared = True
                        clear_s = time.perf_counter() - t0
                        break
                    time.sleep(0.05)
                text = urllib.request.urlopen(
                    srv.url + "/sloz", timeout=10).read().decode()
            finally:
                failpoints.disarm("all")
                introspect.stop()
                slo.disable()
                slo.clear_objectives()
            out["burn_rate_storm"] = {
                "alert_tripped": tripped,
                "trip_after_s": round(trip_s, 2) if trip_s else None,
                "storm_burn_fast": storm_obj["burn_rate"].get("fast"),
                "storm_severity": storm_obj["alert"]["severity"],
                "alert_cleared": cleared,
                "clear_after_s": round(clear_s, 2) if clear_s else None,
                "budget_remaining_after_storm":
                    storm_obj["error_budget_remaining"],
                "tenants_attributed": sorted(z.get("tenants", {})),
                "text_endpoint_renders":
                    "bench_deadline_miss" in text,
            }
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
    return out


def bench_gang_observability():
    """gang_observability block (ISSUE 18, docs/observability.md
    "Gang-wide observability"): the heartbeat-piggybacked metrics
    plane measured three ways —

    - worker-side digest cost: build_digest us/call against live phase
      timers, plus the serialized heartbeat line bytes with the digest
      off (the PR-13 wire, byte-identical) vs on;
    - real-gang heartbeat A/B: the same 2-process training gang run
      digest-off vs digest-on, interleaved; steady-state steps/s from
      the supervisor's step_progress events (warmup excluded). CPU
      caveat: the digest is one bounded JSON dump per 50ms heartbeat
      against a training loop that owns every core, so the delta here
      is noise-bound — the number documents "too small to measure on
      this box", not a speedup claim;
    - straggler drill latency: worker.step=delay(250) armed on rank 1
      only (PADDLE_TPU_FAILPOINTS_RANK1); seconds from gang start to
      the skew score tripping the threshold and to the skew-SLO page.
      Latency is dominated by the scoring window + compressed SLO
      window, not by the digest transport, and says nothing about TPU
      step times — the delay injection is host-side by design.
    """
    import shutil
    import tempfile
    from paddle_tpu import monitor, slo
    from paddle_tpu.flags import get_flag, set_flags
    from paddle_tpu.launch import GangSupervisor, build_digest
    from paddle_tpu.monitor import labeled

    # --- worker-side digest microbench -------------------------------
    for i in range(32):
        monitor.observe_many(timers=[
            (labeled("TIMER_step_phase_us", {"phase": ph}), us + i)
            for ph, us in (("stage", 100.0), ("dispatch", 50.0),
                           ("compute", 800.0), ("exchange", 200.0),
                           ("sync", 40.0), ("total", 1190.0))])
    prev: dict = {}
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        build_digest(step=i, prev=prev)
    build_us = (time.perf_counter() - t0) / n * 1e6

    base = {"rank": 0, "attempt": 0, "pid": 12345,
            "state": "running", "step": 100}
    line_off = len(json.dumps(base)) + 1
    dig = build_digest(step=100, prev={})
    line_on = len(json.dumps(dict(base, digest=dig))) + 1

    out: dict = {
        "build_digest_us_per_call": round(build_us, 2),
        "beat_line_bytes_digest_off": line_off,
        "beat_line_bytes_digest_on": line_on,
        "digest_max_bytes": get_flag("FLAGS_launch_digest_max_bytes"),
    }

    repo = os.path.dirname(os.path.abspath(__file__))
    runner = os.path.join(repo, "tests", "gang_runner.py")
    tmp = tempfile.mkdtemp(prefix="pt_gangobs_bench_")

    def _gang(name, steps, extra_env=None, **kw):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env.update({"GANG_STEPS": str(steps), "GANG_PHASES": "1"})
        env.update(extra_env or {})
        return GangSupervisor(
            [runner], 2, cpu_devices_per_proc=2,
            log_dir=os.path.join(tmp, name), env=env,
            heartbeat_interval_s=0.05, heartbeat_timeout_s=30.0,
            spawn_grace_s=300.0, max_restarts=0,
            name="bench_" + name, **kw)

    def _timed_gang(name, steps, warm=20):
        """Steady-state steps/s from polled supervisor status (the
        step_progress event only marks the FIRST step per incarnation,
        so the rate has to come from the heartbeat-reported step
        counter); warmup steps excluded so spawn + compile time never
        enter the A/B."""
        sup = _gang(name, steps)
        sup.start()
        t0 = s0 = None
        last = (None, 0)
        try:
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline:
                st = sup.status()
                s = max((w["step"] for w in st["workers"]), default=0)
                now = time.monotonic()
                if t0 is None and s >= warm:
                    t0, s0 = now, s
                if s > last[1]:
                    last = (now, s)
                if s >= steps or all(
                        w["state"] in ("exited", "died", "lost")
                        for w in st["workers"]):
                    break
                time.sleep(0.02)
        finally:
            sup.stop()
        t1, s1 = last
        if t0 is None or t1 is None or s1 <= s0 or t1 <= t0:
            return None
        return (s1 - s0) / (t1 - t0)

    old_digest = get_flag("FLAGS_launch_digest")
    try:
        # --- digest on/off A/B (interleaved best-of) -----------------
        STEPS = 300
        off_runs, on_runs = [], []
        for rep in range(2):
            for flag, runs in ((False, off_runs), (True, on_runs)):
                set_flags({"FLAGS_launch_digest": flag})
                sps = _timed_gang("ab_%s_%d" % (flag, rep), STEPS)
                if sps:
                    runs.append(sps)
        set_flags({"FLAGS_launch_digest": old_digest})
        if off_runs and on_runs:
            off_sps, on_sps = max(off_runs), max(on_runs)
            out["heartbeat_ab"] = {
                "workload": "2-process dp gang, %d steps, 50ms "
                            "heartbeats, phase timers on" % STEPS,
                "digest_off_steps_per_sec": round(off_sps, 1),
                "digest_on_steps_per_sec": round(on_sps, 1),
                "overhead_pct": round(
                    (1.0 - on_sps / off_sps) * 100.0, 2),
                "note": "noise-bound on a shared-CPU box; see "
                        "docstring caveat",
            }
        else:
            out["heartbeat_ab"] = {"error": "gang produced no "
                                            "steady-state steps"}

        # --- straggler drill: detection + page latency ---------------
        slo.enable(bucket_s=0.5, n_buckets=240)
        slo.clear_objectives()
        sup = _gang(
            "drill", 8000,
            extra_env={"PADDLE_TPU_FAILPOINTS_RANK1":
                       "worker.step=delay(250)@first(50)"},
            straggler_threshold=2.0, straggler_window_s=1.5)
        sup.start()
        slo.install_gang_objectives(fast_window_s=8.0,
                                    slow_window_s=16.0)
        t_start = time.monotonic()
        detect_s = page_s = None
        try:
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                st = sup.status()
                sc = {w["rank"]: w["straggler_score"]
                      for w in st["workers"]}
                if detect_s is None and (sc.get(1) or 0.0) > 2.0:
                    detect_s = time.monotonic() - t_start
                if detect_s is not None and \
                        "gang_straggler_skew" in slo.evaluate()["firing"]:
                    page_s = time.monotonic() - t_start
                    break
                time.sleep(0.05)
            healthy = sup.status()["workers"]
            healthy = {w["rank"]: w["straggler_score"] for w in healthy}
        finally:
            sup.stop()
            slo.disable()
            slo.clear_objectives()
        out["straggler_drill"] = {
            "injection": "delay(250)@first(50) on rank 1 only",
            "scoring_window_s": 1.5,
            "slo_windows_s": [8.0, 16.0],
            "detect_after_s": round(detect_s, 2) if detect_s else None,
            "page_after_s": round(page_s, 2) if page_s else None,
            "healthy_rank_score": round(healthy.get(0), 2)
            if healthy.get(0) is not None else None,
        }
    except Exception as e:  # noqa: BLE001 - artifact records the failure
        out["error"] = "%s: %s" % (type(e).__name__, e)
    finally:
        set_flags({"FLAGS_launch_digest": old_digest})
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_frontdoor():
    """frontdoor block (ISSUE 20, docs/frontdoor.md): two models — an
    fp32 fc predictor and an int8 generation engine — co-resident in
    ONE process behind a FrontDoor, measured four ways:

    - the disabled path (ns/call): frontdoor.active() with
      FLAGS_frontdoor off is ONE list read (the tracing/failpoints/slo
      contract — a deployment that never constructs a FrontDoor pays
      nothing);
    - priority admission under deliberate overload: a mixed two-tenant
      burst (24 high-priority generous-deadline + 96 low-priority
      tight-deadline requests, interleaved 1:4) against ONE dispatch
      worker, vs the SAME burst in the SAME arrival order through a
      plain FIFO PredictorPool — gates: hi p95 >= 2x lower than FIFO,
      every shed request is low-priority, every hi request completes
      inside its deadline;
    - graceful hot-swap under live traffic: deploy(fc, v2) while 12
      requests are in flight — gates: zero dropped in-flight, the
      routing flip lands (verified over live /modelz HTTP JSON), and
      post-swap steady-state traffic causes ZERO recompiles on either
      endpoint (STAT_executor_compile / STAT_generation_compile deltas);
    - the closed autoscale loop driven by the /sloz signal gauges:
      under a failpoint-slowed queue the controller scales the fc
      endpoint UP toward workers_max, and after drain + hysteresis it
      scales back DOWN — both directions must fire, every decision
      carries the gauge inputs it read.
    """
    import shutil
    import tempfile
    import urllib.request
    import paddle_tpu as pt
    from paddle_tpu import failpoints, frontdoor, introspect, monitor, \
        quant, serving, slo
    from paddle_tpu.flags import set_flags
    from paddle_tpu.frontdoor import (EndpointSpec, FrontDoor,
                                      ModelCatalog, QuotaExceeded)
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest, init_params)
    from paddle_tpu.monitor import stat_get
    from paddle_tpu.serving import DeadlineBurned

    # --- disabled-path microbench ------------------------------------
    set_flags({"FLAGS_frontdoor": False})
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        frontdoor.active()
    active_off_ns = (time.perf_counter() - t0) / n * 1e9

    H_IN = 32
    model_dir = tempfile.mkdtemp(prefix="pt_frontdoor_bench_")
    out: dict = {
        "disabled_active_ns_per_call": round(active_off_ns, 1),
    }
    old_flags = pt.get_flags(["FLAGS_frontdoor_scale_cooldown_s",
                              "FLAGS_frontdoor_quota_burst_s"])
    try:
        # --- the fp32 predictor model (bench_slo's fc stack) ---------
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = pt.layers.data("x", [H_IN])
            h = x
            for _ in range(8):
                h = pt.layers.fc(h, 64, act="relu")
            y = pt.layers.fc(h, 8)
        exe = pt.Executor()
        exe.run(startup)
        pt.io.save_inference_model(model_dir, ["x"], [y], exe,
                                   main_program=main)
        cfg = pt.inference.Config(model_dir)
        cfg.switch_shape_bucketing(True, buckets="pow2:32")

        # --- the int8 generation model -------------------------------
        gcfg = DecoderConfig(vocab_size=128, hidden=64, layers=2,
                             heads=4, max_seq_len=64)
        gq = quant.quantize_decoder_params(init_params(gcfg, seed=0),
                                           "int8")
        mk_engine = lambda: GenerationEngine(  # noqa: E731
            gcfg, gq, num_blocks=64, block_size=8, decode_width=4,
            prefill_chunk=16, prefix_cache=False, quant_mode="int8",
            kv_dtype="int8")

        rng = np.random.RandomState(7)
        feed = lambda b: [rng.rand(b, H_IN).astype(np.float32)]  # noqa: E731

        # --- FIFO baseline: same burst, plain single-model pool ------
        # (max_batch=1 on BOTH sides so the A/B isolates the admission
        # policy, not micro-batch coalescing)
        R, HI_EVERY = 120, 5
        order = [("hi", 10, 2.0) if i % HI_EVERY == 0
                 else ("lo", 0, 0.03) for i in range(R)]
        n_hi = sum(1 for t, _, _ in order if t == "hi")
        payloads = [feed(int(rng.randint(1, 9))) for _ in range(R)]

        # both measured phases run with serving.execute slowed 3ms via
        # failpoint (the bench_slo storm idiom): the same stand-in for
        # a heavier model on both sides, so the A/B isolates the
        # admission policy rather than per-dispatch overhead
        with serving.PredictorPool(pt.inference.create_predictor(cfg),
                                   max_batch=1,
                                   queue_depth=2 * R) as pool:
            pool.warmup([np.zeros((1, H_IN), np.float32)])
            try:
                failpoints.arm_spec("serving.execute=delay(3)")
                for p in payloads[:10]:
                    pool.run(p)
                t0 = time.perf_counter()
                futs = [pool.submit(payloads[i], tenant=order[i][0])
                        for i in range(R)]
                fifo_hi = []
                for i, f in enumerate(futs):
                    f.result()
                    if order[i][0] == "hi":
                        fifo_hi.append(time.perf_counter() - t0)
            finally:
                failpoints.disarm("all")
        fifo_hi_p95 = float(np.percentile(fifo_hi, 95))

        # --- the front door: fc (fp32) + lm (int8) co-resident -------
        catalog = ModelCatalog([
            EndpointSpec(
                name="fc", kind="predictor", version="v1",
                factory=lambda: pt.inference.create_predictor(cfg),
                warmup_feeds=[np.zeros((1, H_IN), np.float32)],
                pool_kwargs={"max_batch": 1, "queue_depth": 2 * R},
                queue_depth=2 * R, workers=1, workers_min=1,
                workers_max=4, tenant_quota_rps={"metered": 5.0}),
            EndpointSpec(
                name="lm", kind="generation", version="v1",
                factory=mk_engine, quant_mode="int8",
                workers=1, workers_min=1, workers_max=2),
        ])
        set_flags({"FLAGS_frontdoor_scale_cooldown_s": 0.0,
                   "FLAGS_frontdoor_quota_burst_s": 2.0})
        srv = introspect.start(port=0)
        door = FrontDoor(catalog, autoscale=False)
        try:
            lm_res = door.run("lm", GenerationRequest(
                prompt=[3, 5, 7] * 4, max_new_tokens=8, request_id=0))

            # --- priority admission under overload ------------------
            shed_tenants: set = set()
            admitted: list = []
            try:
                failpoints.arm_spec("serving.execute=delay(3)")
                # prime the admission EWMAs at the measured service rate
                for p in payloads[:10]:
                    door.run("fc", p)
                t0 = time.perf_counter()
                for i in range(R):
                    tn, prio, dl = order[i]
                    try:
                        admitted.append((i, door.submit(
                            "fc", payloads[i], tenant=tn,
                            priority=prio, deadline=dl)))
                    except (DeadlineBurned, serving.ServingQueueFull):
                        shed_tenants.add(tn)
                fd_hi, lo_done, lo_shed_late = [], 0, 0
                for i, f in admitted:
                    tn = order[i][0]
                    try:
                        f.result(timeout=60.0)
                        if tn == "hi":
                            fd_hi.append(time.perf_counter() - t0)
                        else:
                            lo_done += 1
                    except (DeadlineBurned, TimeoutError):
                        # TimeoutError: dispatched with only a sliver
                        # of deadline budget left, burned inside the
                        # pool — the same deadline shed, raced past
                        # the queue-side check
                        shed_tenants.add(tn)
                        lo_shed_late += 1
            finally:
                failpoints.disarm("all")
            fd_hi_p95 = float(np.percentile(fd_hi, 95)) \
                if len(fd_hi) == n_hi else float("inf")
            hi_met_deadline = (len(fd_hi) == n_hi
                               and max(fd_hi) < 2.0)
            sheds_all_lo = shed_tenants <= {"lo"} and bool(shed_tenants)
            out["priority_overload"] = {
                "workload": "%d requests 1:%d hi:lo, hi prio=10 "
                            "deadline=2s, lo prio=0 deadline=30ms, one "
                            "dispatch worker" % (R, HI_EVERY - 1),
                "fifo_hi_p95_ms": round(fifo_hi_p95 * 1e3, 2),
                "frontdoor_hi_p95_ms": round(fd_hi_p95 * 1e3, 2),
                "hi_p95_speedup_vs_fifo": round(
                    fifo_hi_p95 / fd_hi_p95, 2),
                "hi_completed": len(fd_hi),
                "hi_met_deadline": hi_met_deadline,
                "lo_completed": lo_done,
                "lo_shed_at_admit": R - n_hi - lo_done - lo_shed_late,
                "lo_shed_in_queue": lo_shed_late,
                "shed_tenants": sorted(shed_tenants),
                "sheds_all_low_priority": sheds_all_lo,
            }

            # --- per-tenant token-bucket quota -----------------------
            q_ok = q_rej = 0
            retry_hint = None
            for _ in range(15):
                try:
                    door.submit("fc", payloads[0], tenant="metered")
                    q_ok += 1
                except QuotaExceeded as e:
                    q_rej += 1
                    retry_hint = e.retry_after_s
            out["tenant_quota"] = {
                "quota": "metered @ 5 rps, burst 2s",
                "burst_submits": 15, "admitted": q_ok,
                "rejected": q_rej,
                "retry_after_s_hint": round(retry_hint, 3)
                if retry_hint else None,
            }

            # --- graceful hot-swap under live traffic ----------------
            door.catalog.add(EndpointSpec(
                name="fc", kind="predictor", version="v2",
                factory=lambda: pt.inference.create_predictor(cfg),
                warmup_feeds=[np.zeros((1, H_IN), np.float32)],
                pool_kwargs={"max_batch": 1, "queue_depth": 2 * R},
                queue_depth=2 * R, workers=1, workers_min=1,
                workers_max=4))
            inflight = [door.submit("fc", feed(4)) for _ in range(12)]
            door.deploy("fc", "v2")
            dropped = 0
            for f in inflight:
                try:
                    f.result(timeout=60.0)
                except Exception:
                    dropped += 1
            z = json.load(urllib.request.urlopen(
                srv.url + "/modelz?format=json", timeout=10))
            flip_live = (z["models"]["fc"]["active_version"] == "v2"
                         and z["models"]["fc"]["counters"]["swaps"] == 1)

            # --- zero steady-state recompiles post-swap --------------
            c_exec = stat_get("STAT_executor_compile")
            c_gen = stat_get("STAT_generation_compile")
            for _ in range(40):
                door.run("fc", feed(int(rng.randint(1, 9))))
            for i in range(3):
                door.run("lm", GenerationRequest(
                    prompt=[2, 4, 6] * 4, max_new_tokens=8,
                    request_id=100 + i))
            recompiles = {
                "serving": int(stat_get("STAT_executor_compile")
                               - c_exec),
                "generation": int(stat_get("STAT_generation_compile")
                                  - c_gen),
            }
            out["hot_swap"] = {
                "in_flight_during_swap": len(inflight),
                "dropped_in_flight": dropped,
                "flip_verified_via_modelz_http": flip_live,
                "old_version_drained": z["models"]["fc"]["history"][-1]
                ["state"] == "retired",
                "steady_state_recompiles": recompiles,
            }

            # --- autoscaler: up under pressure, down after drain -----
            slo.enable(bucket_s=0.25, n_buckets=480)
            timeline = [door.model_status()["fc"]["workers"]["target"]]
            decisions = []
            try:
                failpoints.arm_spec("serving.execute=delay(10)")
                backlog = [door.submit("fc", feed(2))
                           for _ in range(30)]
                for _ in range(3):
                    slo.evaluate()
                    decisions += door.autoscale_once()
                    timeline.append(
                        door.model_status()["fc"]["workers"]["target"])
            finally:
                failpoints.disarm("all")
            for f in backlog:
                f.result(timeout=120.0)
            for _ in range(8):
                slo.evaluate()
                decisions += door.autoscale_once()
                timeline.append(
                    door.model_status()["fc"]["workers"]["target"])
            ups = [d for d in decisions if d["action"] == "scale_up"]
            downs = [d for d in decisions
                     if d["action"] == "scale_down"]
            out["autoscaler"] = {
                "signal_gauges": ["GAUGE_slo_queue_depth_trend",
                                  "GAUGE_slo_tpot_saturation",
                                  "GAUGE_slo_kv_block_headroom"],
                "workers_timeline": timeline,
                "scaled_up": len(ups),
                "scaled_down": len(downs),
                "sample_decision": dict(ups[0]) if ups else None,
            }
        finally:
            door.close()
            introspect.stop()
            slo.disable()
        out["int8_generation"] = {
            "quant_mode": "int8", "kv_dtype": "int8",
            "warm_tokens": len(lm_res.tokens),
        }
        out["gates"] = {
            "hi_p95_speedup_ge_2x":
                out["priority_overload"]["hi_p95_speedup_vs_fifo"]
                >= 2.0,
            "sheds_all_low_priority":
                out["priority_overload"]["sheds_all_low_priority"],
            "hi_met_deadline":
                out["priority_overload"]["hi_met_deadline"],
            "hot_swap_zero_dropped":
                out["hot_swap"]["dropped_in_flight"] == 0
                and out["hot_swap"]["flip_verified_via_modelz_http"],
            "zero_steady_state_recompiles": all(
                v == 0 for v in
                out["hot_swap"]["steady_state_recompiles"].values()),
            "autoscaler_up_and_down":
                out["autoscaler"]["scaled_up"] > 0
                and out["autoscaler"]["scaled_down"] > 0,
        }
        out["gates_pass"] = all(out["gates"].values())
    finally:
        set_flags(old_flags)
        shutil.rmtree(model_dir, ignore_errors=True)
    return out


def main():
    """Run the whole bench in THIS process, on the chip. A chip belongs
    to one process, so there is no probe, no retry and no other tier:
    with no TPU this exits non-zero and prints no result."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("ERROR: bench.py measures the chip and jax found platform=%r; "
              "a CPU timing is not a result" % dev.platform,
              file=sys.stderr)
        sys.exit(3)

    (bert_tps, bert_mfu, attn_path, mosaic_ok, bert_b,
     bert_flops, bert_xla_flops) = _bench_bert()
    rn_ips, rn_mfu, rn_flops, rn_xla_flops = _bench_resnet()

    vs = min(bert_mfu, rn_mfu) / 0.45
    rec = {
        "metric": "tokens/sec/chip BERT-base (S=512, masked-LM, bf16) + "
                  "images/sec/chip ResNet-50 (224px, B=256, bf16)",
        "value": round(bert_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs, 4),
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "attention_path": attn_path,
        "mosaic_kernels_in_hlo": bool(mosaic_ok),
        "bert_batch": bert_b,
        "bert_tokens_per_sec": round(bert_tps, 1),
        "bert_mfu": round(bert_mfu, 4),
        "resnet50_images_per_sec": round(rn_ips, 1),
        "resnet50_mfu": round(rn_mfu, 4),
        # both FLOP accountings per step (r7): the analytic hand-count
        # that denominates MFU, and XLA's own count from
        # lowered.cost_analysis() — the ratio documents exactly what
        # the hand-count excludes (embedding lookups, elementwise)
        "bert_flops_per_step_analytic": bert_flops,
        "bert_flops_per_step_xla": bert_xla_flops,
        "bert_flops_xla_over_analytic": round(
            bert_xla_flops / bert_flops, 4)
        if bert_xla_flops and bert_flops else None,
        "resnet_flops_per_step_analytic": rn_flops,
        "resnet_flops_per_step_xla": rn_xla_flops,
        "resnet_flops_xla_over_analytic": round(
            rn_xla_flops / rn_flops, 4)
        if rn_xla_flops and rn_flops else None,
    }
    if not os.environ.get("PT_SKIP_COMPILE_BENCH"):
        # AOT program-cache cold/warm start (CPU compile times are real
        # numbers off-TPU too, unlike MFU — ISSUE 1)
        rec["compile"] = bench_compile()
    if not os.environ.get("PT_SKIP_PIPELINE_BENCH"):
        # async dispatch pipeline: sync vs dispatch-ahead dataset loop
        # (host-overlap is real on CPU too — ISSUE 2)
        rec["pipeline"] = bench_pipeline()
    if not os.environ.get("PT_SKIP_OBS_BENCH"):
        # unified telemetry: disabled-path overhead vs the pipelined
        # baseline + enabled-run trace/stat evidence (ISSUE 3)
        rec["observability"] = bench_observability()
    if not os.environ.get("PT_SKIP_SERVING_BENCH"):
        # serving-grade Predictor: naive vs bucketed vs micro-batched
        # concurrent inference (dispatch amortization is real on CPU
        # too — ISSUE 4)
        rec["serving"] = bench_serving()
    if not os.environ.get("PT_SKIP_GENERATION_BENCH"):
        # autoregressive generation: naive full-context redecode vs
        # paged-KV continuous batching (the KV-cache reuse win is real
        # on CPU too — ISSUE 5)
        rec["generation"] = bench_generation()
    if not os.environ.get("PT_SKIP_GENERATION_PREFIX_BENCH"):
        # cross-request prefix caching: TTFT with a warm cache vs cold
        # recompute of a shared system prompt (the prefill compute
        # saved is real on CPU too — ISSUE 14)
        rec["generation_prefix"] = bench_generation_prefix()
    if not os.environ.get("PT_SKIP_GENERATION_SPEC_BENCH"):
        # speculative decoding: ngram-drafted verify slots riding the
        # mixed step vs plain decode, bitwise-identical streams
        # (ISSUE 14)
        rec["generation_spec"] = bench_generation_spec()
    if not os.environ.get("PT_SKIP_QUANTIZED_SERVING_BENCH"):
        # int8 weights + int8 KV pool vs fp32: logit error budget,
        # >= 2x concurrent sequences at a fixed pool byte budget,
        # greedy stream agreement, zero steady-state recompiles
        # (ISSUE 15 — error and capacity are real on CPU too)
        rec["quantized_serving"] = bench_quantized_serving()
    if not os.environ.get("PT_SKIP_AUTOTUNE_BENCH"):
        # adaptive kernel dispatch: tuned geometry >= 1.15x tokens/s
        # vs the worst eligible candidate and >= 1.0x vs the flag
        # defaults, bitwise streams across forms, zero steady-state
        # recompiles incl. across a policy-reload restart (ISSUE 16)
        rec["autotune"] = bench_autotune()
    if not os.environ.get("PT_SKIP_QUANT_COLLECTIVES_BENCH"):
        # int8 block-scaled gradient exchange vs the synchronous fp32
        # oracle in TrainStep under dp4: >= 3x fewer dp sync bytes
        # (census-verified), int8 overlapped step <= fp32 sync step,
        # 50-step loss budget, zero steady-state recompiles (ISSUE 17)
        rec["quantized_collectives"] = bench_quantized_collectives()
    if not os.environ.get("PT_SKIP_MP_QUANT_COLLECTIVES_BENCH"):
        # mp-axis quantized all-gather composed with Megatron plans
        # under dp4xmp2: zero demotions, >= 3x fewer mp sync bytes
        # (census-verified), 50-step loss budget vs the fp32-composed
        # oracle, zero steady-state recompiles, fp8 where the probe
        # admits; plus a live 2-process dp2xmp2 gang A/B off the
        # per-rank digest plane (ISSUE 19)
        rec["mp_quantized_collectives"] = bench_mp_quant_collectives()
    if not os.environ.get("PT_SKIP_SPMD_BENCH"):
        # mesh-native SPMD runtime: dp scaling + dp4xmp2 loss parity on
        # 8 fake CPU devices; subprocess-isolated because the virtual
        # devices must predate jax backend init (ISSUE 6)
        rec["spmd"] = bench_spmd()
    if not os.environ.get("PT_SKIP_CHAOS_BENCH"):
        # failpoint-driven fault injection + self-healing pools:
        # disarmed-hook cost, zero-delta A/B, fault-storm recovery
        # (ISSUE 9 — all host-side, real on CPU)
        rec["chaos"] = bench_chaos()
    if not os.environ.get("PT_SKIP_CHAOS_MULTIHOST_BENCH"):
        # gang supervisor: kill -9 detection latency + checkpointed
        # BITWISE resume across a real 2-process jax gang (ISSUE 13 —
        # localhost processes stand in for hosts; real on CPU)
        rec["chaos_multihost"] = bench_chaos_multihost()
    if not os.environ.get("PT_SKIP_SLO_BENCH"):
        # windowed SLO engine: disabled-path cost, enabled A/B
        # overhead, burn-rate alert trip/clear under a failpoint
        # deadline-miss storm over live /sloz (ISSUE 12 — host-side,
        # real on CPU)
        rec["slo"] = bench_slo()
    if not os.environ.get("PT_SKIP_GANG_OBS_BENCH"):
        # gang observability plane: digest build cost + wire bytes,
        # digest on/off real-gang heartbeat A/B, straggler drill
        # detection/page latency (ISSUE 18 — host-side, real on CPU)
        rec["gang_observability"] = bench_gang_observability()
    if not os.environ.get("PT_SKIP_FRONTDOOR_BENCH"):
        # multi-tenant multi-model front door: priority admission vs
        # FIFO under overload, quota rejection, zero-drop hot-swap,
        # autoscaler up+down off the /sloz signal gauges (ISSUE 20 —
        # host-side scheduling, real on CPU)
        rec["frontdoor"] = bench_frontdoor()
    print(json.dumps(rec))


if __name__ == "__main__":
    if "--compile-worker" in sys.argv:
        idx = sys.argv.index("--compile-worker")
        if idx + 1 >= len(sys.argv):
            print("usage: bench.py --compile-worker <cache_dir>",
                  file=sys.stderr)
            sys.exit(2)
        _compile_worker(sys.argv[idx + 1])
    elif "--spmd-worker" in sys.argv:
        _spmd_worker()
    elif "--quant-collectives-worker" in sys.argv:
        _quant_collectives_worker()
    elif "--mp-quant-collectives-worker" in sys.argv:
        _mp_quant_collectives_worker()
    else:
        main()
