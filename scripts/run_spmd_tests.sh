#!/usr/bin/env bash
# Run the mesh-native SPMD runtime suite (-m spmd, docs/spmd.md) on the
# 8-device virtual CPU mesh and emit MULTICHIP_r11.json: the usual
# multichip dryrun transcript (same shape as MULTICHIP_r0{1..9}.json)
# plus the mesh plan, the per-axis host-collective census
# (STAT_mesh_collective_<axis>, monitor.py), the chaos smoke
# (failpoints armed over /failpointz, recovery asserted — ISSUE 9),
# the SLO smoke (/sloz text + JSON scraped with per-tenant labeled
# families on /metrics — ISSUE 12), and the multi-process gang smoke
# (2 supervised jax workers, one killed -9 mid-step, bitwise-identical
# resumed loss stream — ISSUE 13), and the quantized-serving smoke
# (int8 checkpoint round-tripped through the conversion path and
# served with the int8 KV pool under the plan — ISSUE 15), and the
# adaptive-dispatch smoke (geometry tuned once, policy scraped from
# /statusz, restart re-serves from the persisted sidecar with zero
# trials / zero recompiles / bitwise streams — ISSUE 16), and the
# quantized-collective smoke (int8 block-scaled gradient exchange in
# TrainStep under the plan: census bytes >= 3x smaller than the fp32
# oracle, loss inside the budget, gauges retract on flag-off rebuild —
# ISSUE 17), and the gang-observability smoke (digest-on gang with a
# rank-targeted delay injection: heartbeat digests land, rank 1's
# straggler score trips, /gangz and /statusz serve the per-rank view —
# ISSUE 18; the full drill incl. the skew-SLO page/clear cycle runs in
# the -m spmd pytest pass above as test_straggler_drill_real_gang),
# and the frontdoor smoke (one fp32 SerializedCore predictor + one
# int8 generation engine co-resident behind the FrontDoor:
# tenant-quota rejection observed, hot-swap flip verified over live
# /modelz JSON — ISSUE 20).
#
# Usage: scripts/run_spmd_tests.sh [extra pytest args...]
set -u
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
# conftest.py also forces this, but the census below runs without pytest
export XLA_FLAGS="$(echo "${XLA_FLAGS:-}" \
    | sed 's/--xla_force_host_platform_device_count=[0-9]*//') \
    --xla_force_host_platform_device_count=8"

echo "== spmd-marked tests (8 virtual CPU devices) =="
python -m pytest tests/ -q -m spmd -p no:cacheprovider "$@"
test_rc=$?

echo "== multichip dryrun + mesh census -> MULTICHIP_r11.json =="
python - "$test_rc" <<'EOF'
import io
import json
import sys
from contextlib import redirect_stdout

test_rc = int(sys.argv[1])
buf = io.StringIO()
rc, err = 0, None
try:
    with redirect_stdout(buf):
        import __graft_entry__ as g
        g.dryrun_multichip(8)
except Exception as e:  # noqa: BLE001 - artifact must record the failure
    rc, err = 1, "%s: %s" % (type(e).__name__, e)

# mesh census: train a real Executor program under a dp4xmp2 plan and
# drive one host-level collective per axis so the per-axis counters in
# the artifact are demonstrably live
import numpy as np
import jax
import paddle_tpu as pt
import paddle_tpu.parallel as dist
from paddle_tpu import layers, monitor
from paddle_tpu.mesh import ShardingPlan, use_plan

plan = ShardingPlan("dp4xmp2")
main, startup = pt.Program(), pt.Program()
with pt.program_guard(main, startup):
    x = layers.data("x", [4])
    y = layers.data("y", [1])
    loss = layers.mean(layers.square_error_cost(
        layers.fc(x, 1, name="p"), y))
    pt.optimizer.SGD(0.05).minimize(loss, startup_program=startup,
                                    program=main)
losses = []
with use_plan(plan):
    exe = pt.Executor()
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        rng = np.random.RandomState(0)
        for _ in range(4):
            xb = rng.randn(16, 4).astype(np.float32)
            yb = (xb.sum(1, keepdims=True)).astype(np.float32)
            out, = exe.run(main, feed={"x": xb, "y": yb},
                           fetch_list=[loss])
            losses.append(float(out))
    dist.init_parallel_env({"dp": 4, "mp": 2})
    dist.all_reduce(np.ones((4,), np.float32), axis="dp")
    dist.all_to_all(np.arange(64, dtype=np.float32).reshape(16, 4),
                    axis="dp")
    dist.all_reduce(np.ones((4,), np.float32), axis="mp")

# introspection smoke (PR 7, /tracez added in PR 8): start the server
# on an ephemeral port, scrape /metrics, /statusz and /tracez (text +
# JSON) from a real HTTP client, assert every paddle_tpu_* family
# parses with a # TYPE line, stop. Proves the serving surface works in
# exactly the multichip environment the rest of this artifact
# documents.
import re
import urllib.request
from paddle_tpu import introspect, tracing

from paddle_tpu.mesh.plan import install_plan

intro = {"ok": False}
try:
    # the server thread reads the PROCESS-GLOBAL plan (use_plan above
    # is thread-local and already exited) — install for the scrape
    install_plan(plan)
    # complete one traced request lifecycle under the mesh so the
    # /tracez scrape below exercises a real record, not an empty ring
    _tr = tracing.begin("serving")
    _tr.stage("admit")
    _tr.finish()
    srv = introspect.start(port=0)
    body = urllib.request.urlopen(srv.url + "/metrics",
                                  timeout=10).read().decode()
    fams = re.findall(r"^# TYPE (paddle_tpu_\S+) (counter|gauge|summary)$",
                      body, re.M)
    sample_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eEinfa]+$")
    samples_ok = all(ln.startswith("#") or sample_re.match(ln)
                     for ln in body.splitlines() if ln)
    statusz = json.load(urllib.request.urlopen(srv.url + "/statusz",
                                               timeout=10))
    tracez_text = urllib.request.urlopen(srv.url + "/tracez",
                                         timeout=10).read().decode()
    tracez = json.load(urllib.request.urlopen(
        srv.url + "/tracez?format=json", timeout=10))
    intro = {
        "ok": bool(fams) and samples_ok
        and statusz["mesh"]["active"] is True
        and tracez["enabled"] is True
        and any(r["trace_id"] == _tr.trace_id
                for r in tracez["recent"])
        and _tr.trace_id in tracez_text,
        "metric_families": len(fams),
        "samples_parse": samples_ok,
        "statusz_mesh": statusz["mesh"],
        "statusz_tracing": statusz.get("tracing"),
        "tracez_recent": len(tracez["recent"]),
        "tracez_rolling_families": sorted(tracez["rolling_us"]),
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    intro["error"] = "%s: %s" % (type(e).__name__, e)
finally:
    introspect.stop()
    install_plan(None)

# chaos smoke (ISSUE 9, docs/robustness.md): arm failpoints over the
# live /failpointz endpoint under the same dp4xmp2 mesh, prove (a) the
# executor surfaces an injected dispatch fault and the very next run
# succeeds, (b) a torn checkpoint write (truncated payload) falls back
# to the previous committed step on load, then assert the cumulative
# hit counts via GET /failpointz — counts survive the auto-disarm.
chaos = {"ok": False}
try:
    import tempfile
    from paddle_tpu.failpoints import InjectedFault
    from paddle_tpu.incubate.checkpoint import AtomicCheckpointer

    install_plan(plan)
    srv = introspect.start(port=0)

    def fp_post(q):
        return json.load(urllib.request.urlopen(
            srv.url + "/failpointz?" + q, data=b"", timeout=10))

    dispatch_faulted = False
    with use_plan(plan):
        exe2 = pt.Executor()
        with pt.scope_guard(pt.Scope()):
            exe2.run(startup)
            # arm AFTER startup: the startup program dispatches too,
            # and @once must spend its one shot on the train step
            fp_post("arm=executor.dispatch=raise@once")
            xb = np.ones((16, 4), np.float32)
            yb = np.ones((16, 1), np.float32)
            try:
                exe2.run(main, feed={"x": xb, "y": yb},
                         fetch_list=[loss])
            except InjectedFault:
                dispatch_faulted = True
            out2, = exe2.run(main, feed={"x": xb, "y": yb},
                             fetch_list=[loss])  # recovered

    ckdir = tempfile.mkdtemp(prefix="pt_chaos_ck_")
    ck = AtomicCheckpointer(ckdir)
    ck.save(1, {"w": np.arange(4.0)})
    fp_post("arm=checkpoint.save=truncate@once")
    ck.save(2, {"w": np.arange(4.0) * 2})  # torn write
    ck_step, _arrays, _m = ck.load_latest()  # must fall back to step 1

    fpz = json.load(urllib.request.urlopen(srv.url + "/failpointz",
                                           timeout=10))["sites"]
    chaos = {
        "ok": dispatch_faulted and np.isfinite(float(out2))
        and ck_step == 1
        and fpz["executor.dispatch"]["fires"] >= 1
        and fpz["checkpoint.save"]["fires"] >= 1
        and fpz["executor.dispatch"]["armed"] is None,
        "dispatch_fault_recovered": dispatch_faulted,
        "checkpoint_fallback_step": ck_step,
        "hit_counts": {s: fpz[s]
                       for s in ("executor.dispatch", "checkpoint.save")},
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    chaos["error"] = "%s: %s" % (type(e).__name__, e)
finally:
    introspect.stop()
    install_plan(None)

# chunked-prefill generation smoke (ISSUE 10, docs/generation.md):
# drive the mixed ragged step under the same dp4xmp2 plan — prompts
# stream through the one fixed-shape executable in chunks while a
# second request decodes, streams must equal the naive full-context
# oracle's, with zero steady-state recompiles after warmup.
generation = {"ok": False}
try:
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest, NaiveGenerator,
                                       SamplingParams, init_params)
    from paddle_tpu.monitor import stat_get

    gcfg = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                         max_seq_len=32)
    gparams = init_params(gcfg, seed=0)
    grng = np.random.RandomState(3)
    greqs = [GenerationRequest(
        prompt=list(grng.randint(1, 64, size=int(n))),
        max_new_tokens=6,
        sampling=SamplingParams(temperature=0.7, seed=i),
        request_id=i) for i, n in enumerate([13, 3, 9, 17])]

    def gen_run(chunk):
        eng = GenerationEngine(gcfg, gparams, num_blocks=64,
                               block_size=4, decode_width=2,
                               prefill_chunk=chunk)
        eng.warmup()
        c0 = stat_get("STAT_generation_compile")
        res = eng.generate(greqs)
        # keyed by request id: the oracle runs one request at a time
        naive = NaiveGenerator(gcfg, gparams, buckets="pow2:32",
                               attn_lanes=eng.attn_lanes)
        return ({r.request_id: r.tokens for r in res},
                {r.request_id: naive.generate(r).tokens for r in greqs},
                int(stat_get("STAT_generation_compile") - c0))

    # PR 14 smokes under the same plan: (a) cross-request prefix
    # caching — a persistent cache-on engine serves the same
    # shared-prefix batch twice; the second (warm) pass must HIT and
    # both passes must equal a cache-off run, keyed by request id.
    # (b) speculative decoding — ngram-drafted verify slots in the
    # mixed step, streams bitwise-identical to plain decode.
    shared = [7, 3, 11, 2, 9, 14, 5, 8]     # two 4-token chunks
    preqs = lambda: [GenerationRequest(
        prompt=shared + [30 + i], max_new_tokens=5,
        sampling=SamplingParams(temperature=0.8, seed=i),
        request_id=i) for i in range(4)]
    sreqs = lambda: [GenerationRequest(
        prompt=[5, 9, 2] * 4, max_new_tokens=8,
        request_id=i) for i in range(2)]

    def mk_eng(**kw):
        kw.setdefault("num_blocks", 64)
        kw.setdefault("block_size", 4)
        kw.setdefault("decode_width", 2)
        kw.setdefault("prefill_chunk", 4)
        return GenerationEngine(gcfg, gparams, **kw)

    with use_plan(plan):
        chunked_toks, naive_toks, chunked_compiles = gen_run(4)

        cold = {r.request_id: r.tokens
                for r in mk_eng(prefix_cache=False).generate(preqs())}
        pfx_eng = mk_eng(prefix_cache=True)
        pass1 = {r.request_id: r.tokens
                 for r in pfx_eng.generate(preqs())}
        h0 = stat_get("STAT_generation_prefix_hits")
        pass2 = {r.request_id: r.tokens
                 for r in pfx_eng.generate(preqs())}
        prefix_hits = int(stat_get("STAT_generation_prefix_hits") - h0)
        prefix_identical = pass1 == cold and pass2 == cold

        plain = {r.request_id: r.tokens
                 for r in mk_eng(prefix_cache=False).generate(sreqs())}
        p0 = stat_get("STAT_generation_spec_proposed")
        a0 = stat_get("STAT_generation_spec_accepted")
        spec = {r.request_id: r.tokens
                for r in mk_eng(prefix_cache=False, spec_tokens=3,
                                draft="ngram").generate(sreqs())}
        spec_proposed = int(
            stat_get("STAT_generation_spec_proposed") - p0)
        spec_accepted = int(
            stat_get("STAT_generation_spec_accepted") - a0)
        spec_identical = spec == plain
    generation = {
        "ok": (chunked_toks == naive_toks and chunked_compiles == 0
               and prefix_identical and prefix_hits > 0
               and spec_identical and spec_proposed > 0),
        "streams_bitwise_identical": chunked_toks == naive_toks,
        "steady_state_recompiles": chunked_compiles,
        "prefill_chunk": 4,
        "chunks": int(sum((len(r.prompt) + 3) // 4 for r in greqs)),
        "tokens_generated": int(sum(len(t) for t in chunked_toks.values())),
        "prefix_warm_pass_hits": prefix_hits,
        "prefix_streams_bitwise_identical": prefix_identical,
        "spec_streams_bitwise_identical": spec_identical,
        "spec_proposed": spec_proposed,
        "spec_accepted": spec_accepted,
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    generation["error"] = "%s: %s" % (type(e).__name__, e)

# quantized-serving smoke (ISSUE 15, docs/quantization.md): round-trip
# a quantized checkpoint through the conversion path, then serve it
# under the same dp4xmp2 plan — int8 weights AND the int8 KV pool in
# the mixed step — and assert the error budget against the fp32
# engine on the same greedy requests, the >= 2x bytes-per-sequence
# capacity win, and that the quant gauges/counters are live.
quant_smoke = {"ok": False}
try:
    import os.path as _qpathmod
    import tempfile as _qtmp
    from paddle_tpu import quant
    from paddle_tpu.monitor import gauge_get

    qpath = _qpathmod.join(_qtmp.mkdtemp(prefix="pt_quant_smoke_"),
                           "ck_int8.npz")
    quant.save_quantized(
        qpath, quant.quantize_decoder_params(gparams, "int8"), "int8")
    qparams, qmode = quant.load_quantized(qpath)

    qreqs = lambda: [GenerationRequest(
        prompt=[(i * 5 + j) % 60 + 1 for j in range(9)],
        max_new_tokens=6, request_id=i) for i in range(4)]
    with use_plan(plan):
        f32_eng = mk_eng(prefix_cache=False)
        f32_toks = {r.request_id: r.tokens
                    for r in f32_eng.generate(qreqs())}
        b0 = stat_get("STAT_generation_kv_quant_blocks")
        q_eng = GenerationEngine(gcfg, qparams, num_blocks=64,
                                 block_size=4, decode_width=2,
                                 prefill_chunk=4, prefix_cache=False,
                                 quant_mode=qmode, kv_dtype="int8")
        # served through the continuous-batching pool, as deployed
        from paddle_tpu.generation import GenerationPool
        with GenerationPool(q_eng) as qpool:
            futs = [(r.request_id, qpool.submit(r)) for r in qreqs()]
            q_toks = {rid: f.result(timeout=120).tokens
                      for rid, f in futs}
        kvq_blocks = int(
            stat_get("STAT_generation_kv_quant_blocks") - b0)
        # the error budget, asserted the way bench.py's
        # quantized_serving block measures it: logits vs the fp32
        # oracle on the same prompts (whole-STREAM equality is not
        # the gate — one near-tie argmax flip legitimately diverges
        # the rest of an untrained model's stream, so streams are
        # reported as agreed-prefix depth instead)
        from paddle_tpu.generation.model import forward_full
        import jax.numpy as jnp
        ptoks = jnp.asarray([r.prompt for r in qreqs()], jnp.int32)
        plens = jnp.asarray([9] * 4, jnp.int32)
        lf = np.asarray(forward_full(gcfg, gparams, ptoks, plens)[0])
        lq = np.asarray(forward_full(gcfg, qparams, ptoks, plens)[0])
        max_abs = float(np.abs(lf - lq).max())
        mse = float(((lf - lq) ** 2).mean())
        greedy_agree = float(
            (lf.argmax(-1) == lq.argmax(-1)).mean())

    def _pfx(a, b):
        n = 0
        for x, y in zip(a, b):
            if x != y:
                break
            n += 1
        return n
    prefixes = [_pfx(f32_toks[i], q_toks[i]) for i in range(4)]
    bytes_ratio = f32_eng.kv_bytes_per_seq() / float(
        q_eng.kv_bytes_per_seq())
    quant_smoke = {
        "ok": (qmode == "int8" and max_abs < 0.25 and mse < 5e-3
               and greedy_agree >= 0.999 and min(prefixes) >= 1
               and bytes_ratio >= 2.0 and kvq_blocks > 0
               and gauge_get("GAUGE_quant_weight_bytes_saved") > 0),
        "mode": qmode,
        "logit_max_abs_delta": round(max_abs, 5),
        "logit_mse": round(mse, 7),
        "greedy_token_agreement": round(greedy_agree, 4),
        "greedy_streams_agree": "%d/4" % sum(
            f32_toks[i] == q_toks[i] for i in range(4)),
        "agreed_prefix_tokens": prefixes,
        "kv_bytes_per_seq_fp32": int(f32_eng.kv_bytes_per_seq()),
        "kv_bytes_per_seq_int8": int(q_eng.kv_bytes_per_seq()),
        "kv_bytes_ratio": round(bytes_ratio, 2),
        "kv_quant_blocks": kvq_blocks,
        "weight_bytes_saved":
            int(gauge_get("GAUGE_quant_weight_bytes_saved")),
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    quant_smoke["error"] = "%s: %s" % (type(e).__name__, e)

# adaptive-dispatch smoke (ISSUE 16, docs/autotune.md): tune the
# ragged-step geometry ONCE under the same dp4xmp2 plan with a tiny
# search budget, read the resolved policy back through /statusz, then
# simulate a process restart (in-memory policy tables cleared) — the
# fresh engine must reload the winner from the persisted sidecar with
# ZERO new trials, ZERO trace-cache misses, zero steady-state
# recompiles after warmup, and bitwise-identical streams.
autotune_smoke = {"ok": False}
try:
    import tempfile as _attmp
    from paddle_tpu import autotune as _at
    from paddle_tpu import flags as _atflags

    _atflags.set_flags({"FLAGS_autotune_candidates": 3,
                        "FLAGS_autotune_probe_tokens": 8})
    _atflags.clear_explicit("FLAGS_autotune_candidates",
                            "FLAGS_autotune_probe_tokens")
    _at.reset()
    _atdir = _attmp.mkdtemp(prefix="pt_autotune_smoke_")
    _atrng = np.random.RandomState(16)
    atreqs = lambda: [GenerationRequest(
        prompt=list(_atrng.randint(1, 64, size=int(n))),
        max_new_tokens=5,
        sampling=SamplingParams(temperature=0.7, seed=i),
        request_id=i) for i, n in enumerate([11, 5, 14, 8])]
    _atrng2 = np.random.RandomState(16)   # same stream for the replay
    atreqs2 = lambda: [GenerationRequest(
        prompt=list(_atrng2.randint(1, 64, size=int(n))),
        max_new_tokens=5,
        sampling=SamplingParams(temperature=0.7, seed=i),
        request_id=i) for i, n in enumerate([11, 5, 14, 8])]

    def at_eng():
        # kernel/block_size pinned via ctor, prefill_chunk left FREE:
        # the tuner searches chunk geometry only (fast, deterministic)
        return GenerationEngine(gcfg, gparams, num_blocks=64,
                                block_size=4, decode_width=2,
                                kernel="reference", autotune=True,
                                program_cache_dir=_atdir)

    with use_plan(plan):
        t0 = stat_get("STAT_autotune_trials")
        eng1 = at_eng()
        eng1.warmup()
        trials = int(stat_get("STAT_autotune_trials") - t0)
        toks1 = {r.request_id: r.tokens for r in eng1.generate(atreqs())}

        # scrape the policy through the live introspection surface
        install_plan(plan)
        srv = introspect.start(port=0)
        atz = json.load(urllib.request.urlopen(
            srv.url + "/statusz", timeout=10))["autotune"]
        introspect.stop()
        install_plan(None)

        # restart: clear the in-memory tables; the sidecar must serve
        _at.reset()
        t1 = stat_get("STAT_autotune_trials")
        m1 = stat_get("STAT_program_cache_trace_miss")
        eng2 = at_eng()
        eng2.warmup()
        c1 = stat_get("STAT_generation_compile")
        toks2 = {r.request_id: r.tokens
                 for r in eng2.generate(atreqs2())}
        at_recompiles = int(stat_get("STAT_generation_compile") - c1)
        retune = int(stat_get("STAT_autotune_trials") - t1)
        at_miss = int(stat_get("STAT_program_cache_trace_miss") - m1)
        src = (eng2._policy_entry or {}).get("source")

    autotune_smoke = {
        "ok": (trials > 0 and bool(atz["policies"])
               and atz["trials"] >= trials and retune == 0
               and at_miss == 0 and at_recompiles == 0
               and src == "disk" and toks1 == toks2),
        "winner": (eng1._policy_entry or {}).get("label"),
        "tune_trials": trials,
        "statusz_policies": len(atz["policies"]),
        "restart_policy_source": src,
        "restart_retune_trials": retune,
        "restart_trace_cache_misses": at_miss,
        "steady_state_recompiles": at_recompiles,
        "streams_bitwise_identical": toks1 == toks2,
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    autotune_smoke["error"] = "%s: %s" % (type(e).__name__, e)

# quantized-collective smoke (ISSUE 17, docs/spmd.md "Quantized
# collectives"): train under the SAME dp4xmp2 plan with
# FLAGS_collective_quant=int8 — params replicated, so the dp axis
# carries the gradient exchange while mp just replicates — and assert
# against the explicit fp32 oracle: the per-step census says the dp
# sync wire shrank >= 3x, the loss trajectory stays inside the 0.05
# budget, the quant instruments are live, and the gauges retract when
# the step rebuilds with the flag off.
collective_quant = {"ok": False}
try:
    from paddle_tpu import nn
    from paddle_tpu.flags import set_flags
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.monitor import gauge_get, get_float_stats

    def _cq_loss(out, label):
        d = out - label
        return (d * d).mean()

    def _cq_build(mode):
        pt.dygraph.seed(0)
        np.random.seed(0)
        set_flags({"FLAGS_collective_quant": mode})
        m = nn.Sequential(nn.Linear(16, 4096), nn.ReLU(),
                          nn.Linear(4096, 8))
        opt = pt.optimizer.SGD(0.05, parameters=m.parameters())
        return TrainStep(m, _cq_loss, opt, plan=plan)

    def _cq_run(mode, steps=6):
        step = _cq_build(mode)
        r = np.random.RandomState(17)
        out = []
        for _ in range(steps):
            xb = r.randn(16, 16).astype(np.float32)
            yb = r.randn(16, 8).astype(np.float32)
            out.append(float(step((xb,), (yb,))))
        return step, out

    with use_plan(plan):
        cq_fp32, losses_fp32 = _cq_run("fp32")
        cq_int8, losses_int8 = _cq_run("int8")
        cq_loss_diff = max(abs(a - b)
                           for a, b in zip(losses_fp32, losses_int8))
        by32 = cq_fp32._coll_manifest["bytes"]
        by8 = cq_int8._coll_manifest["bytes"]
        cq_ratio = sum(by32.values()) / float(sum(by8.values()))
        cq_counters = get_float_stats()
        cq_gauge = gauge_get("GAUGE_collective_quant_wire_bytes")
        # flag-off rebuild retracts the gauges
        _cq_build("off")._build()
        set_flags({"FLAGS_collective_quant": "off"})
        cq_retracted = "GAUGE_collective_quant_buckets" not in \
            monitor.snapshot()["gauges"]
    cq_int8_key = 'STAT_mesh_collective_bytes{axis="dp",dtype="int8"}'
    collective_quant = {
        "ok": (cq_ratio >= 3.0 and cq_loss_diff < 0.05
               and cq_counters.get(cq_int8_key, 0) > 0
               and cq_gauge > 0 and cq_retracted
               and all(np.isfinite(losses_int8))),
        "per_step_sync_bytes_fp32": by32,
        "per_step_sync_bytes_int8": by8,
        "sync_bytes_ratio": round(cq_ratio, 2),
        "loss_max_abs_diff": float(cq_loss_diff),
        "quantized_buckets": cq_int8._coll_manifest["buckets"],
        "int8_wire_counter": cq_counters.get(cq_int8_key, 0),
        "gauges_retract_on_flag_off": cq_retracted,
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    collective_quant["error"] = "%s: %s" % (type(e).__name__, e)
finally:
    from paddle_tpu.flags import set_flags as _cq_restore
    _cq_restore({"FLAGS_collective_quant": "off"})

# mp-axis composed quantized-collective smoke (ISSUE 19, docs/spmd.md
# "Quantized collectives on the mp axis"): a Megatron-ruled MLP under
# dp2xmp2 — l1 column-sharded, l2 row-sharded, head replicated — so
# the mp-axis quantized all-gather composes with the dp gradient wire
# in one build. Asserts ZERO demotions (no warning, no counter
# growth), the per-axis census says the mp gather wire shrank >= 3x
# vs the fp32-composed oracle, the loss trajectory stays inside the
# 0.05 budget, and the steady state never recompiles (the
# out_shardings pin keeps sharded params sharded at rest without a
# spec-spelling cache miss).
mp_collective_quant = {"ok": False}
try:
    import warnings as _mpw
    from jax.sharding import PartitionSpec as _P
    from paddle_tpu import nn
    from paddle_tpu.flags import set_flags
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.monitor import get_float_stats

    def _mpq_rule(name, shape):
        # local shards (16x128 / 128x16 = 2048 elems) span two full
        # quant blocks so block padding doesn't eat the byte ratio
        if shape == (16, 256):
            return _P(None, "mp")
        if shape == (256, 16):
            return _P("mp", None)
        return None

    mpq_plan = ShardingPlan("dp2xmp2", params=_mpq_rule)

    def _mpq_loss(out, label):
        d = out - label
        return (d * d).mean()

    def _mpq_build(mode, mp):
        pt.dygraph.seed(0)
        np.random.seed(0)
        set_flags({"FLAGS_collective_quant": mode,
                   "FLAGS_collective_quant_mp": mp,
                   "FLAGS_collective_quant_min_numel": 16})
        m = nn.Sequential(nn.Linear(16, 256), nn.Tanh(),
                          nn.Linear(256, 16), nn.Tanh(),
                          nn.Linear(16, 8))
        opt = pt.optimizer.SGD(0.05, parameters=m.parameters())
        return TrainStep(m, _mpq_loss, opt, plan=mpq_plan)

    def _mpq_run(mode, mp, steps=6):
        d0 = get_float_stats().get(
            "STAT_collective_quant_demotions", 0.0)
        with _mpw.catch_warnings(record=True) as caught:
            _mpw.simplefilter("always")
            step = _mpq_build(mode, mp)
            r = np.random.RandomState(23)
            out = []
            for _ in range(steps):
                xb = r.randn(8, 16).astype(np.float32)
                yb = r.randn(8, 8).astype(np.float32)
                out.append(float(step((xb,), (yb,))))
        d1 = get_float_stats().get(
            "STAT_collective_quant_demotions", 0.0)
        warned = any("legacy GSPMD" in str(w.message) for w in caught)
        return step, out, int(d1 - d0), warned

    with use_plan(mpq_plan):
        mpq_fp32, mpl_fp32, mpd_fp32, mpw_fp32 = _mpq_run(
            "fp32", "fp32")
        mpq_int8, mpl_int8, mpd_int8, mpw_int8 = _mpq_run(
            "int8", "int8")
    mpq_loss_diff = max(abs(a - b)
                        for a, b in zip(mpl_fp32, mpl_int8))
    mpq_by32 = mpq_fp32._coll_manifest["axes"]["mp"]["bytes"]
    mpq_by8 = mpq_int8._coll_manifest["axes"]["mp"]["bytes"]
    mpq_ratio = sum(mpq_by32.values()) / float(sum(mpq_by8.values()))
    mpq_recompiles = {
        "fp32": mpq_fp32._step_fn._cache_size() - 1,
        "int8": mpq_int8._step_fn._cache_size() - 1,
    }
    mpq_gathers = get_float_stats().get(
        "STAT_collective_quant_mp_gathers", 0.0)
    mp_collective_quant = {
        "ok": (mpq_ratio >= 3.0 and mpq_loss_diff < 0.05
               and mpd_fp32 == 0 and mpd_int8 == 0
               and not (mpw_fp32 or mpw_int8)
               and mpq_recompiles == {"fp32": 0, "int8": 0}
               and mpq_gathers > 0
               and all(np.isfinite(mpl_int8))),
        "mp_gather_params": len(mpq_int8._coll_plan.gathers),
        "per_step_mp_sync_bytes_fp32": mpq_by32,
        "per_step_mp_sync_bytes_int8": mpq_by8,
        "mp_sync_bytes_ratio": round(mpq_ratio, 2),
        "loss_max_abs_diff": float(mpq_loss_diff),
        "demotions": {"fp32": mpd_fp32, "int8": mpd_int8},
        "demotion_warning_fired": bool(mpw_fp32 or mpw_int8),
        "steady_state_recompiles": mpq_recompiles,
        "mp_gather_exchanges": mpq_gathers,
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    mp_collective_quant["error"] = "%s: %s" % (type(e).__name__, e)
finally:
    from paddle_tpu.flags import set_flags as _mpq_restore
    _mpq_restore({"FLAGS_collective_quant": "off",
                  "FLAGS_collective_quant_mp": "off",
                  "FLAGS_collective_quant_min_numel": 2048})

# slo smoke (ISSUE 12, docs/observability.md): enable the windowed SLO
# engine, drive tenant-attributed traced requests (a quarter of them
# deadline-missed), scrape /sloz text + JSON and the tenant-filtered
# /tracez over HTTP, then re-run the /metrics exposition parse with
# labeled per-tenant families present — proves the label-aware
# exporter and the SLO surface work in the same multichip environment.
slo_smoke = {"ok": False}
try:
    from paddle_tpu import slo

    slo.enable(bucket_s=0.25, n_buckets=240)
    slo.clear_objectives()
    slo.register(slo.Objective(
        name="smoke_deadline_miss", kind="ratio", target=0.95,
        bad="STAT_serving_deadline_missed",
        total="STAT_serving_requests",
        window_s=8.0, fast_window_s=2.0, slow_window_s=8.0,
        fast_burn=2.0, slow_burn=3.0))
    for i in range(20):
        t = tracing.begin("serving", tenant="smoke",
                          deadline=(0.0 if i % 4 == 0 else 30.0))
        t.stage("admit")
        monitor.stat_add("STAT_serving_requests")
        t.finish()
    srv = introspect.start(port=0)
    sloz_text = urllib.request.urlopen(srv.url + "/sloz",
                                       timeout=10).read().decode()
    sloz = json.load(urllib.request.urlopen(
        srv.url + "/sloz?format=json", timeout=10))
    tz = json.load(urllib.request.urlopen(
        srv.url + "/tracez?format=json&tenant=smoke", timeout=10))
    body2 = urllib.request.urlopen(srv.url + "/metrics",
                                   timeout=10).read().decode()
    samples2_ok = all(ln.startswith("#") or sample_re.match(ln)
                      for ln in body2.splitlines() if ln)
    n_labeled = sum(1 for ln in body2.splitlines()
                    if 'tenant="smoke"' in ln)
    smoke_obj = next((o for o in sloz["objectives"]
                      if o["name"] == "smoke_deadline_miss"), None)
    slo_smoke = {
        "ok": sloz["enabled"] is True
        and smoke_obj is not None
        and smoke_obj["good_ratio"] is not None
        and "smoke" in sloz["tenants"]
        and "smoke_deadline_miss" in sloz_text
        and samples2_ok and n_labeled > 0
        and len(tz["recent"]) > 0
        and all(r.get("tenant") == "smoke" for r in tz["recent"]),
        "objective_good_ratio":
            None if smoke_obj is None else smoke_obj["good_ratio"],
        "burn_fast": None if smoke_obj is None
        else smoke_obj["burn_rate"].get("fast"),
        "tenants": sorted(sloz["tenants"]),
        "labeled_metric_samples": n_labeled,
        "metrics_parse_with_labels": samples2_ok,
        "tracez_tenant_filtered": len(tz["recent"]),
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    slo_smoke["error"] = "%s: %s" % (type(e).__name__, e)
finally:
    introspect.stop()
    from paddle_tpu import slo as _slo_cleanup
    _slo_cleanup.disable()
    _slo_cleanup.clear_objectives()

# multi-process gang smoke (ISSUE 13, docs/robustness.md "Multi-host
# fault model"): a REAL 2-process jax gang through the supervised
# launcher (paddle_tpu.launch) — kill -9 one rank mid-step; the
# supervisor must detect it, restart the gang from the newest
# checkpoint, and the spliced loss stream must be BITWISE-identical
# to an uninterrupted gang's.
multihost = {"ok": False}
try:
    import os
    import shutil
    import signal
    import tempfile
    import time as _time
    from paddle_tpu.launch import GangSupervisor

    _tmp = tempfile.mkdtemp(prefix="pt_gang_smoke_")

    def _gang(name):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.getcwd() + os.pathsep + \
            env.get("PYTHONPATH", "")
        env["GANG_STEPS"] = "8"
        env["GANG_CK_EVERY"] = "2"
        env["GANG_CKDIR"] = os.path.join(_tmp, "ck_" + name)
        return GangSupervisor(
            [os.path.join("tests", "gang_runner.py")], 2,
            cpu_devices_per_proc=1, log_dir=os.path.join(_tmp, name),
            env=env, heartbeat_interval_s=0.2, heartbeat_timeout_s=30.0,
            spawn_grace_s=300.0, max_restarts=2, restart_backoff_ms=50.0,
            name="smoke_" + name)

    def _losses(name):
        out = {}
        d = os.path.join(_tmp, name)
        for fn in sorted(os.listdir(d)):
            with open(os.path.join(d, fn)) as f:
                for line in f:
                    parts = line.split()
                    if len(parts) == 3 and parts[0] == "STEP":
                        out[int(parts[1])] = parts[2]
        return out

    try:
        _gang("ref").run(timeout=600)
        ref_losses = _losses("ref")

        sup = _gang("chaos")
        sup.start()
        t_kill = None
        try:
            deadline = _time.monotonic() + 480
            while _time.monotonic() < deadline:
                st = sup.status()
                if st["attempt"] == 0 and \
                        max(w["step"] for w in st["workers"]) >= 3:
                    w1 = [w for w in st["workers"]
                          if w["rank"] == 1][0]
                    t_kill = _time.monotonic()
                    os.kill(w1["pid"], signal.SIGKILL)
                    break
                _time.sleep(0.02)
            sup.wait(timeout=600)
        finally:
            sup.stop()
        got = _losses("chaos")
        det = [e for e in sup.events() if t_kill is not None
               and e["t_mono"] >= t_kill
               and e["kind"] in ("worker_death", "worker_lost")]
        bitwise = sorted(got) == sorted(ref_losses) == \
            list(range(1, 9)) and got == ref_losses
        multihost = {
            "ok": bitwise and bool(det),
            "workers": 2,
            "killed_rank": 1,
            "detection_path": det[0]["kind"] if det else None,
            "detection_ms": round((det[0]["t_mono"] - t_kill) * 1e3, 1)
            if det else None,
            "restarts": sup.status()["restarts"],
            "steps": len(got),
            "resume_bitwise_identical": bitwise,
        }
    finally:
        shutil.rmtree(_tmp, ignore_errors=True)
except Exception as e:  # noqa: BLE001 - artifact records the failure
    multihost["error"] = "%s: %s" % (type(e).__name__, e)

# gang-observability smoke (ISSUE 18, docs/observability.md "Gang-wide
# observability"): a digest-on 2-process gang with worker.step=delay
# armed on rank 1 ONLY (rank-targeted env, self-clearing first(N)
# trigger); versioned heartbeat digests with phase timers must land,
# rank 1's straggler score must trip the threshold while the injection
# runs with rank 0 staying healthy, and /gangz + /statusz must serve
# the per-rank view live. The full drill including the skew-SLO
# page/clear cycle runs in the -m spmd pytest pass above.
gang_obs = {"ok": False}
try:
    import os
    import shutil
    import tempfile
    import time as _time
    from paddle_tpu.launch import GangSupervisor

    _gtmp = tempfile.mkdtemp(prefix="pt_gangobs_smoke_")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.getcwd() + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.update({"GANG_STEPS": "4000", "GANG_PHASES": "1",
                "PADDLE_TPU_FAILPOINTS_RANK1":
                    "worker.step=delay(150)@first(40)"})
    sup = GangSupervisor(
        [os.path.join("tests", "gang_runner.py")], 2,
        cpu_devices_per_proc=2, log_dir=os.path.join(_gtmp, "logs"),
        env=env, heartbeat_interval_s=0.05, heartbeat_timeout_s=30.0,
        spawn_grace_s=300.0, max_restarts=0,
        straggler_threshold=2.0, straggler_window_s=1.5,
        name="smoke_obs")
    sup.start()
    tripped = gangz_ok = statusz_ok = False
    digest_v = None
    healthy = {}
    try:
        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline:
            st = sup.status()
            sc = {w["rank"]: w.get("straggler_score")
                  for w in st["workers"]}
            if (sc.get(1) or 0.0) > 2.0:
                tripped = True
                break
            _time.sleep(0.05)
        healthy = {w["rank"]: w.get("straggler_score")
                   for w in sup.status()["workers"]}
        srv = introspect.start(port=0)
        gz = json.load(urllib.request.urlopen(
            srv.url + "/gangz?format=json", timeout=10))
        grow = next(g for g in gz["gangs"] if g["name"] == "smoke_obs")
        w1 = next(w for w in grow["workers"] if w["rank"] == 1)
        digest_v = w1.get("digest_v")
        gangz_ok = digest_v == 1 and bool(w1.get("phases"))
        sz = json.load(urllib.request.urlopen(
            srv.url + "/statusz", timeout=10))
        srow = next(g for g in sz["gangs"] if g["name"] == "smoke_obs")
        statusz_ok = (srow.get("max_straggler") or {}).get("rank") == 1
    finally:
        introspect.stop()
        sup.stop()
        shutil.rmtree(_gtmp, ignore_errors=True)
    gang_obs = {
        "ok": tripped and gangz_ok and statusz_ok
        and (healthy.get(0) is None or healthy[0] < 2.0),
        "straggler_tripped": tripped,
        "healthy_rank_score": healthy.get(0),
        "digest_version": digest_v,
        "gangz_serves_digest": gangz_ok,
        "statusz_max_straggler_rank1": statusz_ok,
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    gang_obs["error"] = "%s: %s" % (type(e).__name__, e)

# frontdoor smoke (ISSUE 20, docs/frontdoor.md): two co-resident
# models in ONE process behind the FrontDoor — an fp32 predictor
# served from an export_serialized() artifact through SerializedCore,
# plus an int8-quantized GenerationEngine — with a tenant-quota
# rejection observed (QuotaExceeded carrying a retry_after_s hint,
# STAT_frontdoor_quota_rejected{model,tenant} bumped) and a graceful
# hot-swap whose routing flip is verified over live /modelz JSON
# (active_version v1 -> v2, zero dropped in-flight requests, the old
# deployment drained to "retired").
frontdoor_smoke = {"ok": False}
try:
    import os
    import shutil
    import tempfile
    from paddle_tpu import frontdoor as fdoor
    from paddle_tpu import quant as _fquant
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationRequest, init_params)

    _ftmp = tempfile.mkdtemp(prefix="pt_frontdoor_smoke_")
    fmain, fstartup = pt.Program(), pt.Program()
    with pt.program_guard(fmain, fstartup):
        fx = layers.data("x", [16])
        fy = layers.fc(layers.fc(fx, 32, act="relu"), 4)
    fexe = pt.Executor()
    fexe.run(fstartup)
    _fdir = os.path.join(_ftmp, "m")
    pt.io.save_inference_model(_fdir, ["x"], [fy], fexe,
                               main_program=fmain)
    _xb = np.zeros((4, 16), np.float32)
    _fart = os.path.join(_ftmp, "art")
    pt.inference.create_predictor(
        pt.inference.Config(_fdir)).export_serialized(_fart, [_xb])

    _gcfg = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=2,
                          max_seq_len=32)
    _gq = _fquant.quantize_decoder_params(
        init_params(_gcfg, seed=0), "int8")
    fcat = fdoor.ModelCatalog([
        fdoor.EndpointSpec(name="fc", kind="predictor", version="v1",
                           model_dir=_fart, warmup_feeds=[_xb],
                           workers=1, workers_min=1, workers_max=2,
                           tenant_quota_rps={"metered": 2.0}),
        fdoor.EndpointSpec(name="fc", kind="predictor", version="v2",
                           model_dir=_fart, warmup_feeds=[_xb],
                           workers=1, workers_min=1, workers_max=2),
        fdoor.EndpointSpec(
            name="lm", kind="generation", version="v1",
            quant_mode="int8", workers=1, workers_min=1,
            workers_max=2,
            factory=lambda: GenerationEngine(
                _gcfg, _gq, num_blocks=32, block_size=8,
                decode_width=2,
                prefill_chunk=8, prefix_cache=False,
                quant_mode="int8", kv_dtype="int8")),
    ])
    door = fdoor.FrontDoor(fcat, autoscale=False)
    try:
        fc_out = door.run("fc", [_xb])
        lm_out = door.run("lm", GenerationRequest(
            prompt=[3, 5, 7, 9], max_new_tokens=4, request_id=0))
        q_rej, retry_hint = 0, None
        for _ in range(8):
            try:
                door.run("fc", [_xb], tenant="metered")
            except fdoor.QuotaExceeded as e:
                q_rej += 1
                retry_hint = e.retry_after_s
        inflight = [door.submit("fc", [_xb]) for _ in range(6)]
        door.deploy("fc", "v2")
        dropped = 0
        for f in inflight:
            try:
                f.result(timeout=60.0)
            except Exception:
                dropped += 1
        srv = introspect.start(port=0)
        mz = json.load(urllib.request.urlopen(
            srv.url + "/modelz?format=json", timeout=10))
        mz_text = urllib.request.urlopen(
            srv.url + "/modelz", timeout=10).read().decode()
    finally:
        introspect.stop()
        door.close()
        shutil.rmtree(_ftmp, ignore_errors=True)
    fc_row = mz["models"]["fc"]
    quota_ctr = sum(v for k, v in monitor.get_float_stats().items()
                    if k.startswith("STAT_frontdoor_quota_rejected"))
    frontdoor_smoke = {
        "ok": (len(fc_out) == 1 and len(lm_out.tokens) > 0
               and q_rej > 0 and quota_ctr >= q_rej and dropped == 0
               and mz["enabled"] is True
               and fc_row["active_version"] == "v2"
               and fc_row["counters"]["swaps"] == 1
               and fc_row["history"][-1]["state"] == "retired"
               and mz["models"]["lm"]["quant_mode"] == "int8"
               and "fc" in mz_text and "lm" in mz_text),
        "fp32_predictor_serves": len(fc_out) == 1,
        "int8_generation_tokens": len(lm_out.tokens),
        "quota_rejected": q_rej,
        "retry_after_s_hint": retry_hint,
        "hot_swap_dropped_in_flight": dropped,
        "modelz_active_version": fc_row["active_version"],
        "modelz_swaps": fc_row["counters"]["swaps"],
    }
except Exception as e:  # noqa: BLE001 - artifact records the failure
    frontdoor_smoke["error"] = "%s: %s" % (type(e).__name__, e)

counters = monitor.get_float_stats()
artifact = {
    "n_devices": len(jax.devices()),
    "rc": rc,
    "ok": rc == 0 and test_rc == 0 and intro.get("ok", False)
    and chaos.get("ok", False) and generation.get("ok", False)
    and quant_smoke.get("ok", False)
    and autotune_smoke.get("ok", False)
    and collective_quant.get("ok", False)
    and mp_collective_quant.get("ok", False)
    and slo_smoke.get("ok", False) and multihost.get("ok", False)
    and gang_obs.get("ok", False)
    and frontdoor_smoke.get("ok", False),
    "skipped": False,
    "spmd_tests_rc": test_rc,
    "mesh_plan": {
        "spec": "dp4xmp2",
        "topology": [list(t) if isinstance(t, tuple) else t
                     for t in plan.topology()],
        "data_axis": plan.data_axis,
        "executor_losses": losses,
    },
    "introspect": intro,
    "chaos": chaos,
    "multihost": multihost,
    "generation": generation,
    "quant": quant_smoke,
    "autotune": autotune_smoke,
    "collective_quant": collective_quant,
    "mp_collective_quant": mp_collective_quant,
    "slo": slo_smoke,
    "gang_observability": gang_obs,
    "frontdoor": frontdoor_smoke,
    "collectives": {k: v for k, v in sorted(counters.items())
                    if k.startswith("STAT_mesh_collective_")},
    "mesh_counters": {k: v for k, v in sorted(counters.items())
                      if k.startswith("STAT_mesh_")},
    "tail": buf.getvalue() + ("" if err is None else err + "\n"),
}
with open("MULTICHIP_r11.json", "w") as f:
    json.dump(artifact, f, indent=1)
    f.write("\n")
print(json.dumps({k: artifact[k] for k in
                  ("n_devices", "rc", "ok", "spmd_tests_rc",
                   "introspect", "chaos", "multihost", "generation",
                   "quant", "autotune", "collective_quant",
                   "mp_collective_quant", "slo",
                   "gang_observability", "frontdoor",
                   "collectives")},
                 indent=1))
sys.exit(0 if artifact["ok"] else 1)
EOF
exit $?
