"""Adaptive kernel dispatch tests (ISSUE 16, docs/autotune.md).

Covers the tentpole contract: candidate enumeration (reference first,
pins respected, budget-bounded, Pallas last), the bitwise eligibility
gate, the one-dict-lookup steady-state resolve, winner persistence in
the program cache's policy/ sidecar (restart round-trip with ZERO new
trials and ZERO new compiles), corruption / version-skew self-healing,
fingerprint isolation across backend/quant-mode keys (mirroring the
PR-15 qm=/kvq= isolation tests), the autotune.measure failpoint
semantics (non-reference fault discards the candidate; reference fault
aborts with nothing persisted — the cache is never poisoned), override
precedence (explicit flags / ctor args pin knobs past any policy), the
scheduler's GAUGE_autotune_* retraction, the Predictor's pad-vs-exact
bucket dispatch, and the /statusz autotune section.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import autotune, failpoints, layers
from paddle_tpu.autotune import CandidateForm, generation_candidates
from paddle_tpu.core import program_cache
from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                   GenerationPool, GenerationRequest,
                                   SamplingParams, init_params)
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.monitor import gauge_get, gauge_set, stat_get

CFG = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                    max_seq_len=32)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=0)


@pytest.fixture(autouse=True)
def _clean_slate():
    """Small search budget + tiny probe so tunes stay test-sized, a
    fresh in-memory policy table per test, flags restored."""
    from paddle_tpu import flags as F
    saved, saved_exp = dict(F._values), set(F._EXPLICIT)
    F.set_flags({"FLAGS_autotune_candidates": 3,
                 "FLAGS_autotune_probe_tokens": 8})
    F.clear_explicit("FLAGS_autotune_candidates",
                     "FLAGS_autotune_probe_tokens")
    autotune.reset()
    failpoints.disarm()
    yield
    F._values.clear()
    F._values.update(saved)
    F._EXPLICIT.clear()
    F._EXPLICIT.update(saved_exp)
    autotune.reset()
    failpoints.disarm()


def _engine(params, **kw):
    # kernel + block_size pinned by default so tunes search the cheap
    # prefill_chunk dimension only (no Pallas-interpret trials)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("decode_width", 2)
    kw.setdefault("kernel", "reference")
    kw.setdefault("block_size", 4)
    kw.setdefault("autotune", True)
    return GenerationEngine(CFG, params, **kw)


def _gen(eng, n=2, new=4, seed=7):
    rng = np.random.default_rng(seed)
    streams = {}
    for i in range(n):
        prompt = [int(t) for t in rng.integers(0, CFG.vocab_size, 5)]
        eng.submit(GenerationRequest(
            prompt=prompt, max_new_tokens=new,
            sampling=SamplingParams(temperature=0.8, top_k=5,
                                    seed=100 + i),
            request_id="r%d" % i))
    for _ in range(200):
        if eng.idle:
            break
        for r in eng.step():
            streams[r.request_id] = tuple(r.tokens)
    assert eng.idle
    return streams


def _trace_entries(cache_dir):
    d = os.path.join(cache_dir, "trace")
    return set(os.listdir(d)) if os.path.isdir(d) else set()


def _policy_files(cache_dir):
    d = os.path.join(cache_dir, "policy")
    return [os.path.join(d, f) for f in sorted(os.listdir(d))] \
        if os.path.isdir(d) else []


# ---------------------------------------------------------------------------
# candidate enumeration
# ---------------------------------------------------------------------------

def test_candidates_reference_first_budget_and_pallas_last():
    d = CandidateForm("reference", 16, 8, 0)
    cands = generation_candidates(d, pins={}, budget=10)
    assert cands[0] == d                      # reference form is #1
    assert len(cands) == len(set(cands))      # deduped
    pallas = [c for c in cands if c.kernel == "pallas"]
    assert pallas == [cands[-1]]              # kernel flip ordered last
    # a small budget searches geometry only — Pallas never trialed
    small = generation_candidates(d, pins={}, budget=3)
    assert len(small) == 3
    assert all(c.kernel == "reference" for c in small)


def test_candidates_respect_pins():
    d = CandidateForm("reference", 16, 8, 0)
    cands = generation_candidates(
        d, pins={"prefill_chunk": 8, "kernel": "reference"}, budget=10)
    assert all(c.prefill_chunk == 8 for c in cands)
    assert all(c.kernel == "reference" for c in cands)
    assert any(c.block_size != 16 for c in cands)  # free dim varies


# ---------------------------------------------------------------------------
# steady-state resolve is ONE dict lookup
# ---------------------------------------------------------------------------

def test_resolve_is_one_dict_lookup():
    calls = []

    class Counting(dict):
        def get(self, *a, **kw):
            calls.append(a)
            return dict.get(self, *a, **kw)

    pol = autotune.policy()
    orig = pol._table
    try:
        pol._table = Counting(orig)
        pol._table["k"] = {"label": "x"}
        calls.clear()
        assert pol.resolve("k") == {"label": "x"}
        assert len(calls) == 1
    finally:
        pol._table = orig


# ---------------------------------------------------------------------------
# tune -> persist -> restart round-trip
# ---------------------------------------------------------------------------

def test_winner_survives_restart_zero_trials_zero_compiles(
        tmp_path, params):
    cache = str(tmp_path / "pcache")
    eng = _engine(params, program_cache_dir=cache)  # chunk left free
    assert eng._policy_entry is not None
    assert eng._policy_entry["source"] == "tuned"
    assert eng._policy_entry["trials"] >= 2
    assert len(_policy_files(cache)) == 1
    eng.warmup()
    streams = _gen(eng)
    traces = _trace_entries(cache)
    assert traces

    # "restart": clear the in-memory table; a fresh engine must reload
    # the winner from disk and re-tune / recompile NOTHING
    autotune.reset()
    t0 = stat_get("STAT_autotune_trials")
    m0 = stat_get("STAT_program_cache_trace_miss")
    eng2 = _engine(params, program_cache_dir=cache)
    assert stat_get("STAT_autotune_trials") == t0
    assert eng2._policy_entry["source"] == "disk"
    assert eng2._policy_entry["label"] == eng._policy_entry["label"]
    eng2.warmup()
    assert stat_get("STAT_program_cache_trace_miss") == m0
    assert _trace_entries(cache) == traces
    assert _gen(eng2) == streams              # bitwise across restart


def test_policy_entry_rides_program_fingerprint(tmp_path, params):
    """Two engines resolving DIFFERENT forms never share AOT entries:
    the resolved kernel+policy label is part of the v=4 program meta."""
    cache = str(tmp_path / "pcache")
    a = _engine(params, autotune=False, prefill_chunk=4,
                program_cache_dir=cache)
    a.warmup()
    ea = _trace_entries(cache)
    b = _engine(params, autotune=False, prefill_chunk=8,
                program_cache_dir=cache)
    b.warmup()
    assert _trace_entries(cache) - ea         # pc8 exported NEW entries


# ---------------------------------------------------------------------------
# corruption / version skew self-heal
# ---------------------------------------------------------------------------

def test_corrupt_policy_file_self_heals(tmp_path, params):
    cache = str(tmp_path / "pcache")
    eng = _engine(params, prefill_chunk=4, program_cache_dir=cache)
    label = eng._policy_entry["label"]
    [pf] = _policy_files(cache)
    with open(pf, "wb") as f:
        f.write(b"garbage\x00not json")
    autotune.reset()
    c0 = stat_get("STAT_program_cache_corrupt")
    t0 = stat_get("STAT_autotune_trials")
    eng2 = _engine(params, prefill_chunk=4, program_cache_dir=cache)
    assert stat_get("STAT_program_cache_corrupt") == c0 + 1
    assert stat_get("STAT_autotune_trials") > t0    # re-tuned
    assert eng2._policy_entry["source"] == "tuned"
    assert eng2._policy_entry["label"] == label
    # the healed file round-trips again
    autotune.reset()
    eng3 = _engine(params, prefill_chunk=4, program_cache_dir=cache)
    assert eng3._policy_entry["source"] == "disk"


def test_version_skewed_policy_file_retunes(tmp_path, params):
    cache = str(tmp_path / "pcache")
    _engine(params, prefill_chunk=4, program_cache_dir=cache)
    [pf] = _policy_files(cache)
    with open(pf, "rb") as f:
        blob = f.read()
    assert blob.startswith(program_cache.POLICY_MAGIC)
    rest = blob[len(program_cache.POLICY_MAGIC):]
    nl = rest.index(b"\n")
    hdr = json.loads(rest[:nl])
    hdr["format"] = program_cache.POLICY_FORMAT_VERSION + 1
    with open(pf, "wb") as f:
        f.write(program_cache.POLICY_MAGIC
                + json.dumps(hdr).encode() + b"\n" + rest[nl + 1:])
    autotune.reset()
    t0 = stat_get("STAT_autotune_trials")
    _engine(params, prefill_chunk=4, program_cache_dir=cache)
    assert stat_get("STAT_autotune_trials") > t0    # skew -> re-tune


# ---------------------------------------------------------------------------
# fingerprint isolation (mirrors the PR-15 qm=/kvq= tests)
# ---------------------------------------------------------------------------

def test_policy_fingerprint_isolates_backend_and_quant_keys():
    base = {"kind": "generation", "backend": "cpu", "qm": "off"}
    fp = program_cache.policy_fingerprint(base)
    assert fp != program_cache.policy_fingerprint(
        dict(base, backend="tpu"))
    assert fp != program_cache.policy_fingerprint(dict(base, qm="int8"))
    assert fp == program_cache.policy_fingerprint(dict(base))


def test_quant_modes_never_share_a_policy(tmp_path, params):
    cache = str(tmp_path / "pcache")
    e32 = _engine(params, prefill_chunk=4, program_cache_dir=cache)
    assert len(_policy_files(cache)) == 1
    t0 = stat_get("STAT_autotune_trials")
    e8 = _engine(params, prefill_chunk=4, quant_mode="int8",
                 program_cache_dir=cache)
    # the int8 key missed the fp32 policy: it tuned its own entry
    assert stat_get("STAT_autotune_trials") > t0
    assert len(_policy_files(cache)) == 2
    assert e8._policy_entry is not e32._policy_entry
    snap = autotune.policies()
    assert {s["qm"] for s in snap if s["kind"] == "generation"} == \
        {"off", "int8"}


def test_pinned_kernel_rides_the_policy_key_meta(tmp_path, params):
    """No flag names the kernel form, so the policy's key holds no
    knob the policy chooses; a form pinned by `kernel=` rides the key
    meta's pins and isolates its own entry."""
    from paddle_tpu.flags import lowering_snapshot
    assert not [k for k, _ in lowering_snapshot() if "paged" in k]
    meta = {"kind": "generation", "backend": "cpu", "pins": {}}
    pinned = dict(meta, pins={"kernel": "pallas"})
    assert program_cache.policy_fingerprint(meta) != \
        program_cache.policy_fingerprint(pinned)
    _engine(params, program_cache_dir=str(tmp_path / "c"))
    pins = [json.loads(k)["pins"] for k in autotune._POLICY._table]
    assert pins and all(p["kernel"] == "reference" for p in pins)


# ---------------------------------------------------------------------------
# autotune.measure failpoint
# ---------------------------------------------------------------------------

def test_reference_trial_fault_aborts_nothing_persisted(
        tmp_path, params):
    cache = str(tmp_path / "pcache")
    failpoints.arm("autotune.measure", "raise", "once")
    f0 = stat_get("STAT_autotune_fallbacks")
    w0 = stat_get("STAT_autotune_wins")
    eng = _engine(params, prefill_chunk=4, program_cache_dir=cache)
    assert stat_get("STAT_autotune_fallbacks") == f0 + 1
    assert stat_get("STAT_autotune_wins") == w0          # no winner
    assert eng._policy_entry is None
    assert _policy_files(cache) == []                    # not poisoned
    assert autotune.policies() == []
    # the engine still serves on the reference/default form
    eng.warmup()
    assert eng.prefill_chunk == 4
    assert _gen(eng)


def test_candidate_fault_discards_candidate_reference_wins(
        tmp_path, params):
    cache = str(tmp_path / "pcache")
    # fire on every trial AFTER the reference trial
    failpoints.arm("autotune.measure", "raise", "after(1)")
    f0 = stat_get("STAT_autotune_fallbacks")
    eng = _engine(params, program_cache_dir=cache)  # chunk free
    e = eng._policy_entry
    assert e is not None
    assert e["prefill_chunk"] == 8            # reference form won
    assert stat_get("STAT_autotune_fallbacks") > f0
    dead = [c for c in e["candidates"] if not c["eligible"]]
    assert dead and all("error" in c for c in dead)
    assert len(_policy_files(cache)) == 1     # winner still persisted


# ---------------------------------------------------------------------------
# override precedence: flags / ctor args pin past any policy
# ---------------------------------------------------------------------------

def test_explicit_flag_pins_knob_out_of_search(params):
    from paddle_tpu import flags as F
    F.set_flags({"FLAGS_generation_prefill_chunk": 4})
    assert F.explicitly_set("FLAGS_generation_prefill_chunk")
    eng = _engine(params)                     # no ctor chunk arg
    assert eng.prefill_chunk == 4             # the pin held
    e = eng._policy_entry
    assert e is not None
    assert all(c["prefill_chunk"] == 4 for c in e["candidates"])


def test_default_flag_is_not_a_pin(params):
    # a flag at its DEFAULT does not pin: the tuner varies the chunk
    from paddle_tpu import flags as F
    assert not F.explicitly_set("FLAGS_generation_prefill_chunk")
    eng = _engine(params)                     # chunk left free
    e = eng._policy_entry
    assert e is not None
    chunks = {c["prefill_chunk"] for c in e["candidates"]}
    assert len(chunks) > 1                    # search varied the knob


def test_autotune_off_is_legacy_behavior(params):
    t0 = stat_get("STAT_autotune_trials")
    eng = _engine(params, autotune=False, prefill_chunk=4)
    assert eng._policy_entry is None
    assert stat_get("STAT_autotune_trials") == t0
    assert eng.prefill_chunk == 4


# ---------------------------------------------------------------------------
# gauges + scheduler retraction
# ---------------------------------------------------------------------------

def test_engine_publishes_and_reset_engine_retracts_gauges(params):
    eng = _engine(params)
    assert gauge_get("GAUGE_autotune_active") == 1
    assert gauge_get("GAUGE_autotune_trials") >= 2
    plain = _engine(params, autotune=False, prefill_chunk=4)
    pool = GenerationPool(plain, _start=False)
    try:
        gauge_set("GAUGE_autotune_active", 1)
        gauge_set("GAUGE_autotune_step_time_us", 123.0)
        gauge_set("GAUGE_autotune_trials", 9)
        pool._reset_engine()
        assert gauge_get("GAUGE_autotune_active") == 0
        assert gauge_get("GAUGE_autotune_step_time_us") == 0
        assert gauge_get("GAUGE_autotune_trials") == 0
    finally:
        pool.close()


# ---------------------------------------------------------------------------
# kernel_form override is trace-scoped, not process-global
# ---------------------------------------------------------------------------

def test_kernel_form_override_scoped_and_restored():
    assert pa.resolved_form() == "reference"
    with pa.kernel_form("pallas"):
        assert pa.resolved_form() == "pallas"
        with pa.kernel_form(None):            # None passes through
            assert pa.resolved_form() == "pallas"
    assert pa.resolved_form() == "reference"


# ---------------------------------------------------------------------------
# Predictor bucket dispatch
# ---------------------------------------------------------------------------

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


def test_predictor_bucket_dispatch_tunes_then_one_lookup(
        model_dir, tmp_path):
    cache = str(tmp_path / "pcache")
    cfg = Config(model_dir)
    cfg.switch_shape_bucketing(True, buckets=[1, 2, 4, 8])
    cfg.switch_autotune(True)
    cfg.enable_program_cache(cache)
    p = create_predictor(cfg)
    feed = np.random.RandomState(0).randn(3, 6).astype(np.float32)
    t0 = stat_get("STAT_autotune_trials")
    out1 = p.run([feed])[0]
    assert stat_get("STAT_autotune_trials") > t0
    snap = [s for s in autotune.policies() if s["kind"] == "predictor"]
    assert snap and snap[0]["form"] in ("bucket", "exact")
    assert snap[0]["rows"] == 3 and snap[0]["bucket"] == 4
    # steady state: policy hit, zero new trials, bitwise-stable output
    t1 = stat_get("STAT_autotune_trials")
    h0 = stat_get("STAT_autotune_cache_hits")
    out2 = p.run([feed])[0]
    assert stat_get("STAT_autotune_trials") == t1
    assert stat_get("STAT_autotune_cache_hits") == h0 + 1
    assert np.array_equal(out1, out2)
    # restart: a fresh predictor reloads the persisted winner
    autotune.reset()
    p2 = create_predictor(cfg)
    t2 = stat_get("STAT_autotune_trials")
    out3 = p2.run([feed])[0]
    assert stat_get("STAT_autotune_trials") == t2
    assert np.array_equal(out1, out3)


def test_predictor_autotune_off_never_tunes(model_dir):
    cfg = Config(model_dir)
    cfg.switch_shape_bucketing(True, buckets=[1, 2, 4, 8])
    p = create_predictor(cfg)
    t0 = stat_get("STAT_autotune_trials")
    p.run([np.zeros((3, 6), np.float32)])
    assert stat_get("STAT_autotune_trials") == t0


def test_predictor_reference_fault_keeps_bucket_form(model_dir):
    cfg = Config(model_dir)
    cfg.switch_shape_bucketing(True, buckets=[1, 2, 4, 8])
    cfg.switch_autotune(True)
    cfg.disable_program_cache()
    p = create_predictor(cfg)
    failpoints.arm("autotune.measure", "raise", "once")
    out = p.run([np.ones((3, 6), np.float32)])[0]
    assert out.shape[0] == 3
    assert autotune.policies() == []          # nothing installed
    failpoints.disarm()
    # exact-row b==bucket shapes never consult the policy at all
    t0 = stat_get("STAT_autotune_trials")
    p.run([np.ones((4, 6), np.float32)])
    assert stat_get("STAT_autotune_trials") == t0


# ---------------------------------------------------------------------------
# /statusz section
# ---------------------------------------------------------------------------

def test_statusz_autotune_section(params):
    from paddle_tpu import introspect
    _engine(params, prefill_chunk=4)
    s = introspect.statusz()["autotune"]
    assert set(s) >= {"enabled", "policies", "trials", "wins",
                      "cache_hits", "fallbacks"}
    assert s["trials"] >= 2 and s["wins"] >= 1
    forms = [p["form"] for p in s["policies"]]
    assert any("bs4" in f for f in forms)


# ---------------------------------------------------------------------------
# stat_diff cost family
# ---------------------------------------------------------------------------

def test_stat_diff_flags_retuning_loop_not_cache_hits():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "stat_diff", os.path.join(os.path.dirname(__file__), "..",
                                  "tools", "stat_diff.py"))
    sd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sd)
    assert sd._is_cost_counter("STAT_autotune_trials")
    assert sd._is_cost_counter("STAT_autotune_wins")
    assert sd._is_cost_counter("STAT_autotune_fallbacks")
    assert not sd._is_cost_counter("STAT_autotune_cache_hits")
