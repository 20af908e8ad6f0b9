"""Quantized serving path (ISSUE 15, docs/quantization.md).

Tentpole coverage: the shared absmax scale contract (quantize /
dequantize round trips, dead channels included), the int8 x int8 ->
int32 -> scale matmul within the logit error budget across all four
samplers, the quantized KV block pool fused into the ragged mixed step
(reference AND Pallas-interpret forms), composition with PR 14's
prefix cache + copy-on-write + speculative decoding (greedy streams
agree with fp32 on short contexts), program-cache fingerprint
isolation (an fp32 entry can never serve a quantized checkpoint), and
fp32 purity (quant off keeps the EXACT pre-quant expressions at the
matmul/embed seams).

Error budgets mirror bench.py's quantized_serving block: max-abs logit
delta, MSE, and greedy-token agreement vs the fp32 oracle.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers, quant
from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                   GenerationRequest, SamplingParams,
                                   init_params)
from paddle_tpu.generation.model import forward_full
from paddle_tpu.inference import Config, Predictor
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.monitor import gauge_get, stat_get

CFG = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                    max_seq_len=64)

# the harness budget (bench.py quantized_serving uses the same gates,
# scaled): tiny-model logits live in ~[-4, 4]; int8 per-channel weights
# land well inside these
MAX_ABS_BUDGET = 0.25
MSE_BUDGET = 5e-3


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=0)


@pytest.fixture(scope="module")
def qparams(params):
    return quant.quantize_decoder_params(params, "int8")


def _engine(params, **kw):
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 4)
    kw.setdefault("decode_width", 2)
    kw.setdefault("prefill_chunk", 4)
    return GenerationEngine(CFG, params, **kw)


def _reqs(sampling_list, n_tok=8):
    return [GenerationRequest(request_id=i, prompt=[(i + 1) % 7 + 1] * 5,
                              max_new_tokens=n_tok, sampling=sp)
            for i, sp in enumerate(sampling_list)]


# ---------------------------------------------------------------------------
# scale contract
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_and_dead_channel():
    rng = np.random.default_rng(0)
    w = rng.normal(size=(16, 8)).astype(np.float32)
    w[:, 3] = 0.0                          # dead output channel
    q, s = quant.quantize_array(w, 1, "int8")
    assert np.asarray(q).dtype == np.int8 and s.shape == (8,)
    assert float(s[3]) == 1.0              # guarded, stored verbatim
    back = np.asarray(quant.dequantize_array(q, s, 1))
    assert np.abs(back - w).max() <= float(np.max(s)) / 254 + 1e-9
    assert np.all(back[:, 3] == 0.0)       # dead channel exact
    # idempotent conversion
    p = {"w": jnp.asarray(w), "w" + quant.SCALE_SUFFIX: s}
    assert quant.quantize_decoder_params(p, "int8") == p


def test_qat_adapters_are_lossless_inverses(qparams):
    back = quant.from_qat(quant.to_qat(qparams))
    assert set(back) == set(qparams)
    for k in qparams:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(qparams[k]))


def test_save_load_roundtrip(tmp_path, qparams):
    path = str(tmp_path / "q.npz")
    quant.save_quantized(path, qparams, "int8")
    back, mode = quant.load_quantized(path)
    assert mode == "int8" and set(back) == set(qparams)
    for k in qparams:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(qparams[k]))


def test_convert_cli_demo(tmp_path):
    from paddle_tpu.quant.convert import main
    out = str(tmp_path / "demo.npz")
    assert main(["--demo", "--out", out, "--mode", "int8"]) == 0
    p, mode = quant.load_quantized(out)
    assert mode == "int8" and quant.is_quantized(p)
    assert quant.weight_bytes_saved(p) > 0


# ---------------------------------------------------------------------------
# fp32 purity: absent scales keep the EXACT original expressions
# ---------------------------------------------------------------------------

def test_fp32_seams_are_bitwise_noops(params):
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(3, CFG.hidden)), jnp.float32)
    w = params["l0_wqkv"]
    np.testing.assert_array_equal(
        np.asarray(quant.matmul(params, "l0_wqkv", x)),
        np.asarray(x @ w))
    idx = jnp.asarray([0, 5, 2], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(quant.embed(params, "tok_emb", idx)),
        np.asarray(params["tok_emb"][idx]))


def test_quant_off_engine_keeps_fp32_state(params):
    eng = _engine(params, quant_mode="off")
    assert eng.quant_mode == "off" and eng.kv_dtype == "fp32"
    assert eng.k_pools.dtype == jnp.float32 and eng.k_scales is None
    assert not quant.is_quantized(eng.params)
    assert gauge_get("GAUGE_quant_weight_bytes_saved") == 0


# ---------------------------------------------------------------------------
# logit error budget
# ---------------------------------------------------------------------------

def test_int8_logits_within_budget(params, qparams):
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, size=(4, 24)),
                       jnp.int32)
    lens = jnp.asarray([24, 13, 6, 1], jnp.int32)
    lf = np.asarray(forward_full(CFG, params, toks, lens)[0])
    lq = np.asarray(forward_full(CFG, qparams, toks, lens)[0])
    d = lf - lq
    assert np.abs(d).max() < MAX_ABS_BUDGET
    assert (d ** 2).mean() < MSE_BUDGET
    # greedy tokens agree everywhere on these short contexts
    assert np.array_equal(lf.argmax(-1), lq.argmax(-1))


# ---------------------------------------------------------------------------
# quantized KV fused into the mixed step
# ---------------------------------------------------------------------------

def test_kv_dequant_reference_vs_pallas_interpret():
    rng = np.random.default_rng(3)
    B, H, D, N, bs, M = 3, 4, 8, 16, 4, 4
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(N, bs, H, D)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(N, bs, H, D)), jnp.float32)
    kq, ks = quant.quantize_kv_rows(kf, jnp.int8)
    vq, vs = quant.quantize_kv_rows(vf, jnp.int8)
    tables = jnp.asarray(rng.integers(1, N, size=(B, M)), jnp.int32)
    ctx = jnp.asarray([5, 9, 1], jnp.int32)
    ref = pa.paged_attention_reference(q, kq, vq, tables, ctx,
                                       k_scales=ks, v_scales=vs)
    pal = pa.paged_attention_pallas(q, kq, vq, tables, ctx,
                                    k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=0, atol=1e-5)
    # and the dequant error vs true fp32 K/V stays small
    f32 = pa.paged_attention_reference(q, kf, vf, tables, ctx)
    assert float(jnp.max(jnp.abs(ref - f32))) < 0.05


def test_quantized_kv_requires_chunked_mode(params):
    # the two-phase engine, which refused a quantized pool, is gone
    with pytest.raises(ValueError, match="prefill_chunk must be >= 1"):
        _engine(params, prefill_chunk=0, kv_dtype="int8")


def test_all_four_samplers_within_budget(params):
    """greedy / temperature / top-k / top-p: the quantized engine is
    deterministic per (seed, step) like fp32, stays within the logit
    budget (greedy agrees exactly on short contexts), and the
    stochastic samplers emit valid tokens through the int8 matmuls."""
    samplers = [SamplingParams(temperature=0.0),
                SamplingParams(temperature=0.8, seed=7),
                SamplingParams(temperature=0.9, top_k=8, seed=11),
                SamplingParams(temperature=0.9, top_p=0.8, seed=13)]

    def run(p, **kw):
        eng = _engine(p, decode_width=4, **kw)
        out = eng.generate(_reqs(samplers))
        return {r.request_id: r.tokens for r in out}, eng

    fp32, _ = run(params)
    q1, eng = run(params, quant_mode="int8")
    q2, _ = run(params, quant_mode="int8")
    assert eng.quant_mode == "int8" and eng.kv_dtype == "int8"
    assert q1 == q2                       # deterministic replay
    assert q1[0] == fp32[0]               # greedy agrees with fp32
    for i in range(len(samplers)):        # valid tokens everywhere
        assert all(0 <= t < CFG.vocab_size for t in q1[i])
    assert stat_get("STAT_generation_kv_quant_blocks") > 0
    assert gauge_get("GAUGE_kv_bytes_per_seq") == eng.kv_bytes_per_seq()
    assert gauge_get("GAUGE_quant_weight_bytes_saved") > 0


def test_composes_with_prefix_cache_cow_and_spec_decode(params):
    """The PR-14 stack (cross-request prefix cache, copy-on-write,
    speculative decoding) over a QUANTIZED pool: greedy streams match
    the fp32 engine running the same stack, COW clones carry the scale
    rows, and the prefix hits really happened."""
    shared = [3] * 8                       # shared prefix, 2 blocks
    def reqs():
        # the third, admitted once a lane frees, repeats the 8 cached
        # tokens exactly: its re-run last token copies the last block
        return [GenerationRequest(request_id=i,
                                  prompt=shared + [i + 1] * 2 * (i < 2),
                                  max_new_tokens=8,
                                  sampling=SamplingParams(seed=i))
                for i in range(3)]

    def run(p, **kw):
        eng = _engine(p, prefix_cache=True, spec_tokens=2, **kw)
        out = eng.generate(reqs())
        return {r.request_id: r.tokens for r in out}, eng

    h0 = stat_get("STAT_generation_prefix_hits")
    c0 = stat_get("STAT_generation_prefix_cow_copies")
    fp32, _ = run(params)
    q, eng = run(params, quant_mode="int8")
    assert q == fp32
    assert eng.k_scales is not None
    assert stat_get("STAT_generation_prefix_hits") > h0
    assert stat_get("STAT_generation_prefix_cow_copies") > c0


def test_quantized_kv_capacity_headline(params):
    """At the same pool dims, int8 KV (payload + scales) costs under
    half the fp32 bytes per sequence — the >= 2x concurrent-sequence
    headline bench.py gates at a fixed byte budget."""
    e32 = _engine(params)
    e8 = _engine(params, quant_mode="int8")
    assert e8.kv_bytes_per_seq() * 2 <= e32.kv_bytes_per_seq()
    assert e8.kv_pool_bytes() * 2 <= e32.kv_pool_bytes()


# ---------------------------------------------------------------------------
# program-cache fingerprint isolation
# ---------------------------------------------------------------------------

def _trace_entries(cache_dir):
    d = os.path.join(cache_dir, "trace")
    return set(os.listdir(d)) if os.path.isdir(d) else set()


def test_fp32_and_int8_never_share_a_cache_entry(tmp_path, params):
    cache = str(tmp_path / "pcache")
    e32 = _engine(params, program_cache_dir=cache)
    e32.warmup()
    fp32_entries = _trace_entries(cache)
    assert fp32_entries                    # fp32 exported something
    e8 = _engine(params, quant_mode="int8", program_cache_dir=cache)
    e8.warmup()
    int8_entries = _trace_entries(cache) - fp32_entries
    assert int8_entries                    # int8 exported NEW entries
    assert not (fp32_entries & int8_entries)
    # steady state: a fresh engine of either flavor adds nothing
    before = _trace_entries(cache)
    _engine(params, quant_mode="int8", program_cache_dir=cache).warmup()
    _engine(params, program_cache_dir=cache).warmup()
    assert _trace_entries(cache) == before


# ---------------------------------------------------------------------------
# Predictor (program/scope) path
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


def test_predictor_int8_within_budget(model_dir):
    xb = np.random.default_rng(4).normal(size=(5, 6)).astype(np.float32)
    ref = Predictor(Config(model_dir)).run([xb])[0]
    cfg = Config(model_dir)
    cfg.enable_quant("int8")
    pred = Predictor(cfg)
    # scope really holds int8 weights + persistable absmax scales
    int8_vars = [n for b in pred.program.blocks
                 for n, v in b.vars.items() if v.dtype == "int8"]
    assert int8_vars
    for n in int8_vars:
        s = np.asarray(pred.scope.find_var(n + ".quant_scale"))
        assert s.dtype == np.float32 and np.all(s > 0)
    assert gauge_get("GAUGE_quant_weight_bytes_saved") > 0
    out = pred.run([xb])[0]
    d = np.asarray(out) - np.asarray(ref)
    assert np.abs(d).max() < 0.1 and (d ** 2).mean() < 1e-3
    assert pred._prog_tag(8).endswith("_int8")   # /programz tag


def test_serialized_core_serves_quantized_export(tmp_path, model_dir):
    """export_serialized from a quantized Predictor: the traced
    computation already contains the int8 weights + dequant ops, so
    the framework-free SerializedCore serves the quantized model with
    no Program IR — and stays within the Predictor's own budget."""
    from paddle_tpu.inference import SerializedPredictor
    xb = np.random.default_rng(6).normal(size=(5, 6)).astype(np.float32)
    cfg = Config(model_dir)
    cfg.enable_quant("int8")
    pred = Predictor(cfg)
    ref = np.asarray(pred.run([xb])[0])
    art = str(tmp_path / "qart")
    pred.export_serialized(art, [xb])
    out = np.asarray(SerializedPredictor(art).run([xb])[0])
    np.testing.assert_allclose(out, ref, rtol=0, atol=1e-6)


def test_statusz_quant_section(params):
    from paddle_tpu.introspect import statusz
    _engine(params, quant_mode="int8")          # publishes the gauges
    s = statusz()["generation"]["quant"]
    assert set(s) >= {"mode", "kv_dtype", "kv_capacity_seqs",
                      "kv_bytes_per_seq", "weight_bytes_saved",
                      "kv_quant_blocks"}
    assert s["weight_bytes_saved"] > 0
