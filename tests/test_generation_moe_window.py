"""The EXPERT decoder family through the generation engine
(generation/moe_window.py; docs/generation.md, "Model families"):
routed experts of which this program holds a share, window and full
attention layers in one paged pool, grouped key-value heads.

On XLA:CPU at tiny widths: the window and the grouped heads in both
kernel forms against a dense loop, the paged forward against the
full-context one and against the benchmark's plain reference (and six
planted faults seen to differ), the eight shares adding up to the
uncut layer, no token dropped under the worst routing, the accepted
families' streams with a window of 0, the counters against a hand
count, the scopes of the compiled step, and the size of the lowered
step against depth.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import k_exaone_236b as R
from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                   GenerationRequest, KVCacheManager,
                                   NaiveGenerator, SamplingParams,
                                   init_params)
from paddle_tpu.generation import looped, model, moe_window as mw
from paddle_tpu.generation.moe_window import ExpertDecoderConfig
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.monitor import stat_get

FORMS = pytest.mark.parametrize("form", ["reference", "pallas"])
WINDOW = 8


def _source(sparse=3, held=(4, 8), **kw):
    """A toy `config.json` in the source's keys, cut as the benchmark's
    file is: `num_experts` the experts held, the router's width beside
    it."""
    n = 1 + sparse
    kinds = ["sliding_attention", "sliding_attention", "sliding_attention",
             "full_attention"]
    src = {
        "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": n,
        "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
        "intermediate_size": 80, "moe_intermediate_size": 16,
        "num_experts": held[1], "num_experts_published": 16,
        "experts_held": {"first": held[0], "count": held[1]},
        "num_experts_per_tok": 4, "num_shared_experts": 1,
        "routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "hidden_act": "silu", "rms_norm_eps": 1e-5,
        "layer_types": [kinds[i % 4] for i in range(n)],
        "mlp_layer_types": ["dense"] + ["sparse"] * sparse,
        "sliding_window": WINDOW,
        "rope_parameters": {"rope_theta": 1e6, "rope_type": "default"},
        "max_position_embeddings": 262144}
    src.update(kw)
    return src


def _cfg(src=None, max_context=48):
    src = src or _source()
    held = src["experts_held"]
    return ExpertDecoderConfig.from_source(src, max_context,
                                           (held["first"], held["count"]))


def _engine(cfg, params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("decode_width", 4)
    kw.setdefault("prefill_chunk", 8)
    return GenerationEngine(cfg, params, **kw)


def _reqs(n=5, new=8):
    sps = [SamplingParams(), SamplingParams(temperature=0.8, seed=101),
           SamplingParams(temperature=0.9, top_k=8, seed=202)]
    return [GenerationRequest(prompt=list(range(3 + i, 14 + 4 * i)),
                              max_new_tokens=new, request_id=i,
                              sampling=sps[i % len(sps)])
            for i in range(n)]


# ---------------------------------------------------------------------------
# the kernel: a window and grouped key-value heads, both forms
# ---------------------------------------------------------------------------

def _dense_attention(q, kp, vp, tables, visible, layer, window):
    """A loop in numpy: slot b's query heads over key-value head h //
    rep, keys `max(0, n - window) .. n - 1` of its `n` visible."""
    b, h, d = q.shape
    hkv = kp.shape[-1] // d
    out = np.zeros((b, h, d))
    for i in range(b):
        n = int(visible[i])
        ks = np.asarray(kp[layer, tables[i]]).reshape(-1, hkv, d)[:n]
        vs = np.asarray(vp[layer, tables[i]]).reshape(-1, hkv, d)[:n]
        lo = max(0, n - window) if window else 0
        for hh in range(h):
            g = hh // (h // hkv)
            s = ks[lo:, g] @ np.asarray(q[i, hh]) / np.sqrt(d)
            p = np.exp(s - s.max())
            out[i, hh] = (p / p.sum()) @ vs[lo:, g]
    return out


def _pool_case(bs=4, entries=40, slots=8, hkv=2, d=8, heads=8, seed=0):
    rng = np.random.default_rng(seed)
    n = slots * entries + 1
    shape = (3, n, bs, hkv * d)
    kp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, n))
                         .reshape(slots, entries), jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, heads, d)), jnp.float32)
    return q, kp, vp, tables


@FORMS
@pytest.mark.parametrize("window", [0, 5, 32, 48])
def test_window_and_grouped_heads_against_a_dense_loop(form, window):
    """Blocks of 4, so the Pallas form's loop step is 32 blocks = 128
    positions; contexts that end mid-block (1, 7, 133), a window that
    starts mid-block (visible 7 - 5, 130 - 32), contexts under the
    window (1, 3), a first live group past group 0 (133 - 5 = 128: the
    groups before it are skipped) and a window that straddles two
    groups (150 - 32 = 118)."""
    q, kp, vp, tables = _pool_case()
    visible = jnp.asarray([1, 3, 7, 32, 130, 133, 150, 160], jnp.int32)
    with pa.kernel_form(form):
        got = jax.jit(lambda w: pa.paged_attention(
            q, kp, vp, tables, visible, layer=jnp.int32(1), window=w))(
                jnp.int32(window))
    want = _dense_attention(q, kp, vp, tables, visible, 1, window)
    assert np.abs(np.asarray(got) - want).max() <= 2e-6


@FORMS
def test_a_prefill_chunk_that_crosses_the_windows_edge(form):
    """The ragged entry: a chunk of 6 queries at positions 5..10 under
    a window of 8. Queries 0..2 still see key 0; queries 3..5 have lost
    keys 0..2: each query's own edge, not the chunk's."""
    q, kp, vp, tables = _pool_case(slots=2)
    cq = 6
    qc = jnp.stack([q[:, :, :]] * cq, axis=1) * jnp.arange(
        1, cq + 1, dtype=jnp.float32)[None, :, None, None]
    ctx = jnp.asarray([5, 127], jnp.int32)
    with pa.kernel_form(form):
        got = np.asarray(pa.ragged_paged_attention(
            qc, kp, vp, tables, jnp.asarray([cq, cq], jnp.int32), ctx,
            layer=2, window=jnp.int32(WINDOW)))
    for j in range(cq):
        want = _dense_attention(qc[:, j], kp, vp, tables, ctx + j + 1, 2,
                                WINDOW)
        assert np.abs(got[:, j] - want).max() <= 2e-6, j


@FORMS
def test_window_zero_is_the_program_without_a_window_bit_for_bit(form):
    q, kp, vp, tables = _pool_case(hkv=8)
    visible = jnp.asarray([1, 3, 7, 32, 130, 133, 150, 160], jnp.int32)
    with pa.kernel_form(form):
        plain = pa.paged_attention(q, kp, vp, tables, visible, layer=1)
        zero = pa.paged_attention(q, kp, vp, tables, visible, layer=1,
                                  window=jnp.int32(0))
    assert np.array_equal(np.asarray(plain), np.asarray(zero))


@pytest.mark.parametrize("family", ["gpt2", "ouro"])
@FORMS
def test_the_accepted_families_streams_with_a_window_of_zero(
        family, form, monkeypatch):
    """`gpt2` and `ouro` at toy sizes: their engines' token streams with
    every `paged_attention` call given a traced window of 0 are the
    streams without a window, token for token."""
    if family == "gpt2":
        cfg = DecoderConfig(vocab_size=96, hidden=32, layers=2, heads=4,
                            max_seq_len=48)
        params, mod = init_params(cfg, seed=3), model
    else:
        cfg = looped.LoopedDecoderConfig(
            vocab_size=96, hidden_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, head_dim=12,
            intermediate_size=80, total_ut_steps=2, max_seq_len=48)
        params, mod = looped.init_params(cfg, seed=3), looped

    def streams():
        eng = _engine(cfg, params, kernel=form)
        return {r.request_id: r.tokens for r in eng.generate(_reqs())}
    before = streams()
    real = mod.paged_attention
    calls = []

    def windowed(*a, **kw):
        calls.append(1)
        return real(*a, window=jnp.int32(0), **kw)
    monkeypatch.setattr(mod, "paged_attention", windowed)
    assert streams() == before and calls


# ---------------------------------------------------------------------------
# paged against full-context, and the engine against the oracle
# ---------------------------------------------------------------------------

# float32 rounding through the layers (tests/test_generation_looped.py,
# _PAGED_ATOL, has the reason rows of a matmul move with their batch);
# the router's choice is discrete, and a tie within rounding would flip
# an expert: none does at these seeds.
_PAGED_ATOL = 3e-5


@FORMS
def test_paged_prefill_chunks_then_decode_equal_forward_full(form):
    cfg = _cfg()
    params = mw.init_params(cfg, seed=1)
    bs, m, nblk, chunk = 4, 12, 40, 5
    lanes = m * bs
    rng = np.random.default_rng(0)
    lens = np.array([11, 7, 14])       # 11 and 14 outgrow the window of 8
    toks = rng.integers(0, cfg.vocab_size, (3, 24)).astype(np.int32)
    ff = jax.jit(lambda p, t, l: cfg.forward_full(p, t, l,
                                                  attn_lanes=lanes))
    with pa.kernel_form(form):
        step = jax.jit(cfg.forward_paged)
        mgr = KVCacheManager(nblk, bs)
        shape = (cfg.kv_layers, nblk, bs, cfg.kv_row)
        kp = vp = jnp.zeros(shape, jnp.float32)
        tables = np.zeros((3, m), np.int32)
        for i in range(3):
            mgr.alloc(i, mgr.blocks_for_tokens(24))
            tables[i] = mgr.table(i, m)
        done = np.zeros(3, int)
        logits_at = {}
        while (done < lens).any():
            rows, pos, tok, owner = [], [], [], []
            for i in range(3):
                for j in range(done[i], min(done[i] + chunk, lens[i])):
                    rows.append(tables[i]); pos.append(j)
                    tok.append(toks[i, j]); owner.append(i)
            pad = 15 - len(rows)
            rows += [np.zeros(m, np.int32)] * pad
            pos += [0] * pad
            tok += [0] * pad
            lg, kp, vp = step(params, kp, vp, jnp.asarray(np.stack(rows)),
                              jnp.asarray(pos, jnp.int32),
                              jnp.asarray(tok, jnp.int32))
            for s, i in enumerate(owner):
                logits_at[(i, pos[s])] = np.asarray(lg[s])
            done = np.minimum(done + chunk, lens)
        worst = 0.0
        for i in range(3):
            for j in (0, lens[i] // 2, lens[i] - 1):
                oracle, _, _ = ff(params, jnp.asarray(toks[i:i + 1]),
                                  jnp.asarray([j + 1]))
                worst = max(worst, np.abs(logits_at[(i, j)]
                                          - np.asarray(oracle[0])).max())
        cl = lens.copy()
        for _ in range(6):
            nxt = np.array([toks[i, cl[i]] for i in range(3)], np.int32)
            lg, kp, vp = step(
                params, kp, vp,
                jnp.asarray(np.concatenate(
                    [tables, np.zeros((12, m), np.int32)])),
                jnp.asarray(np.concatenate([cl, np.zeros(12)]), jnp.int32),
                jnp.asarray(np.concatenate([nxt, np.zeros(12)]),
                            jnp.int32))
            cl = cl + 1
            oracle, kc, vc = ff(params, jnp.asarray(toks), jnp.asarray(cl))
            worst = max(worst, np.abs(np.asarray(lg[:3])
                                      - np.asarray(oracle)).max())
    assert worst <= _PAGED_ATOL, worst
    # the cache the paged path built is the full-context one: rows of
    # kv_heads x head_dim, NOT of the 8 query heads
    assert kc.shape == (cfg.kv_layers, 3, 24, 2, 8)
    for i in range(3):
        for p in (0, int(cl[i]) - 1):
            got = np.asarray(kp[:, tables[i][p // bs], p % bs])
            want = np.asarray(kc[:, i, p]).reshape(cfg.kv_layers, -1)
            assert np.abs(got - want).max() <= _PAGED_ATOL


@FORMS
def test_engine_streams_equal_the_naive_generators(form):
    cfg = _cfg()
    params = mw.init_params(cfg, seed=2)
    eng = _engine(cfg, params, kernel=form)
    naive = NaiveGenerator(cfg, params, attn_lanes=eng.attn_lanes)
    reqs = _reqs()
    got = {r.request_id: r.tokens for r in eng.generate(reqs)}
    for r in reqs:
        assert got[r.request_id] == naive.generate(r).tokens, r.request_id


# ---------------------------------------------------------------------------
# against the benchmark's plain reference
# ---------------------------------------------------------------------------

# float32 on both sides (the reference at precision "highest", which on
# the CPU is what the program computes too): rounding alone through
# four layers. The planted faults read above 1e-2.
_REF_ATOL = 5e-5


def _paged_logits(cfg, params, toks, form="reference", chunk=5):
    """[T, V]: the program's logits at every position of one row,
    through the paged cache: the prompt in chunks of `chunk` slots,
    then a token a step."""
    bs, m = 4, 12
    shape = (cfg.kv_layers, 16, bs, cfg.kv_row)
    kp = vp = jnp.zeros(shape, jnp.float32)
    table = np.arange(1, m + 1, dtype=np.int32)
    out = []
    with pa.kernel_form(form):
        step = jax.jit(cfg.forward_paged)
        at, t = 0, len(toks)
        while at < t:
            n = chunk if at + chunk <= t // 2 else 1
            rows = np.zeros((chunk, m), np.int32)
            rows[:n] = table
            pos = np.zeros(chunk, np.int32)
            pos[:n] = np.arange(at, at + n)
            tok = np.zeros(chunk, np.int32)
            tok[:n] = toks[at:at + n]
            lg, kp, vp = step(params, kp, vp, jnp.asarray(rows),
                              jnp.asarray(pos), jnp.asarray(tok))
            out.extend(np.asarray(lg[:n]))
            at += n
    return np.stack(out)


def _reference_logits(src, params, toks, fault=None):
    z = R.sizes(src)
    out = jax.jit(lambda p, t: R.forward(p, z, t, 0, len(toks), "float32",
                                         fault=fault))(
        params, jnp.asarray(toks, jnp.int32))
    return np.asarray(out)


@FORMS
def test_program_logits_equal_the_benchmark_references(form):
    src = _source()
    cfg = _cfg(src)
    # the benchmark's own weights, under the names the engine reads
    params = R.make_weights(src, 7, dtype=jnp.float32)
    assert {k: v.shape for k, v in params.items()} == \
        {k: v.shape for k, v in mw.init_params(cfg).items()}
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 24)
    want = _reference_logits(src, params, toks)
    got = _paged_logits(cfg, params, toks, form)
    assert np.abs(got - want).max() <= _REF_ATOL
    assert np.abs(want).max() > 0.5         # logits have a scale
    # and the full-context forward, the oracle of the stream tests
    rows = jnp.asarray(np.tile(toks, (24, 1)), jnp.int32)
    full, _, _ = jax.jit(cfg.forward_full)(
        params, rows, jnp.arange(1, 25, dtype=jnp.int32))
    assert np.abs(np.asarray(full) - want).max() <= _REF_ATOL


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_fault_planted_in_the_reference_is_seen(fault):
    """The choice bias dropped, the normalisation taken over the held
    experts only, `routed_scaling_factor` dropped, the window off by
    one, rotary applied on the full layer, key-value head `h % rep` for
    `h // rep`: each bends one side, and the two no longer agree."""
    src = _source()
    cfg = _cfg(src)
    params = R.make_weights(src, 7, dtype=jnp.float32)
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 24)
    got = _paged_logits(cfg, params, toks)
    bad = _reference_logits(src, params, toks, fault=fault)
    assert np.abs(got - bad).max() > 200 * _REF_ATOL, fault


# ---------------------------------------------------------------------------
# the chip's share
# ---------------------------------------------------------------------------

def _sparse_layer(params, at, first=None, held=None):
    """The leaves of sparse layer `at`; with `first`, the experts'
    leaves cut to `first .. first + held - 1`."""
    w = {n: params[n][at] for n in ("router", "router_bias", "s_gu",
                                    "s_down", "e_gu", "e_down")}
    if first is not None:
        w["e_gu"] = w["e_gu"][first:first + held]
        w["e_down"] = w["e_down"][first:first + held]
    return w


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The guide's share test. A layer with all 16 experts held, by the
    reference; then eight shares of 2 experts each, by the PROGRAM
    (`moe`, told which experts it holds): the shares' routed parts and
    the shared expert counted once are the uncut layer."""
    whole_src = _source(held=(0, 16))
    params = R.make_weights(whole_src, 5, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(20, 32)),
                    jnp.float32)
    want = np.asarray(R.whole_layer(whole_src, _sparse_layer(params, 1), x))
    shared = np.asarray(R.whole_layer(
        whole_src, _sparse_layer(params, 1, 0, 0), x, held=0))
    total = np.zeros_like(want)
    for share in range(8):
        src = _source(held=(2 * share, 2))
        cfg = _cfg(src)
        w = _sparse_layer(params, 1, 2 * share, 2)
        # the program takes the experts' leaves WHOLE, [layers, held, ..]
        w.update(e_gu=w["e_gu"][None], e_down=w["e_down"][None], at=0)
        out, load = jax.jit(lambda w, x: mw.moe(
            cfg, w, x, jnp.ones((20,), bool)))(w, x)
        total += np.asarray(out) - shared       # its routed part alone
        # and the reference, given the same share, gives the same part
        ref = R.whole_layer(src, _sparse_layer(params, 1, 2 * share, 2), x,
                            first=2 * share, held=2)
        assert np.abs(np.asarray(out) - np.asarray(ref)).max() <= 1e-5
    assert np.abs(total + shared - want).max() <= 2e-5
    assert np.abs(want - shared).max() > 0.1    # the routed part is there


def test_no_token_is_dropped_when_every_token_chooses_one_expert():
    """The worst imbalance: a router whose bias sends EVERY token to
    experts 4..7, all held here. All 20 x 4 pairs are computed (the
    loads say so, and the output is the reference's), at the shape the
    even case has."""
    src = _source()
    cfg = _cfg(src)
    params = dict(R.make_weights(src, 5, dtype=jnp.float32))
    bias = np.zeros((3, 16), np.float32)
    bias[:, 4:8] = 10.0
    params["router_bias"] = jnp.asarray(bias)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(20, 32)),
                    jnp.float32)
    w = _sparse_layer(params, 2)
    out, load = jax.jit(lambda w, x: mw.moe(
        cfg, dict(w, e_gu=params["e_gu"], e_down=params["e_down"], at=2),
        x, jnp.ones((20,), bool)))(w, x)
    assert np.asarray(load).tolist() == [20, 20, 20, 20, 0, 0, 0, 0]
    want = R.whole_layer(src, w, x, first=4, held=8)
    assert np.abs(np.asarray(out) - np.asarray(want)).max() <= 1e-5
    # a slot that is not live is neither counted nor computed
    live = jnp.arange(20) < 7
    out2, load2 = jax.jit(lambda w, x: mw.moe(
        cfg, dict(w, e_gu=params["e_gu"], e_down=params["e_down"], at=2),
        x, live))(w, x)
    assert np.asarray(load2).tolist() == [7, 7, 7, 7, 0, 0, 0, 0]
    assert np.abs(np.asarray(out2[:7]) - np.asarray(want[:7])).max() <= 1e-5


# ---------------------------------------------------------------------------
# counters, scopes, the program's size
# ---------------------------------------------------------------------------

def test_the_counters_against_a_hand_count():
    """One request of 11 prompt tokens and 3 new ones through a 4-layer
    model (windows 8, 8, 8, none) with blocks of 4, its routing read
    from the program's own router."""
    src = _source()
    cfg = _cfg(src)
    params = mw.init_params(cfg, seed=4)
    eng = _engine(cfg, params, prefix_cache=False, prefill_chunk=16,
                  lookahead=0)
    names = ("STAT_generation_attended_tokens",
             "STAT_generation_attended_blocks", "STAT_generation_moe_pairs",
             "STAT_generation_moe_experts_touched",
             "STAT_generation_moe_peak_load")
    before = [stat_get(n) for n in names]
    prompt = list(range(20, 31))
    eng.submit(GenerationRequest(prompt=prompt, max_new_tokens=3))
    eng.step()          # the 11 prompt tokens, positions 0..10
    got = [stat_get(n) - b for n, b in zip(names, before)]
    # the full layer: position p sees p + 1 keys; a window layer at most 8
    full = sum(p + 1 for p in range(11))
    win = sum(min(p + 1, 8) for p in range(11))
    assert got[0] == full + 3 * win == 66 + 3 * 60
    # blocks of 4: the full layer spans p // 4 + 1, a window layer the
    # blocks from the first visible key's to the position's
    fb = sum(p // 4 + 1 for p in range(11))
    wb = sum(p // 4 - max(p - 7, 0) // 4 + 1 for p in range(11))
    assert got[1] == fb + 3 * wb
    # the routing, by the program's own router on the stream it serves:
    # recompute the layers' inputs with the full-context forward
    toks = jnp.asarray([prompt], jnp.int32)
    loads = _loads_of(cfg, params, toks)
    assert got[2] == loads.sum() and got[2] > 0
    assert got[3] == (loads > 0).sum()
    assert got[4] == loads.max(axis=1).sum()


def test_context_rows_count_a_lanes_context_once_a_layer():
    """`STAT_generation_context_rows`: the rows a lane's slots see together,
    once each, summed over the layers (windows 8, 8, 8, none). The chunk of
    positions 0..10 sees all 11 rows in every layer; the decode slot at 11
    sees 12 in the full layer and its window of 8 in the others."""
    cfg = _cfg(_source())
    eng = _engine(cfg, mw.init_params(cfg, seed=4), prefix_cache=False,
                  prefill_chunk=16, lookahead=0)
    eng.submit(GenerationRequest(prompt=list(range(20, 31)),
                                 max_new_tokens=3))
    r0 = stat_get("STAT_generation_context_rows")
    eng.step()
    assert stat_get("STAT_generation_context_rows") - r0 == 4 * 11
    eng.step()
    assert stat_get("STAT_generation_context_rows") - r0 == 4 * 11 + 12 \
        + 3 * 8


def _loads_of(cfg, params, toks):
    """[sparse layers, experts held]: the held experts' loads over the
    tokens of `toks`, from `forward_paged` one token a step."""
    bs, m = 4, 12
    kp = vp = jnp.zeros((cfg.kv_layers, 16, bs, cfg.kv_row), jnp.float32)
    table = jnp.asarray(np.arange(1, m + 1, dtype=np.int32)[None])
    step = jax.jit(lambda p, k, v, pos, tok: cfg.forward_paged(
        p, k, v, table, pos, tok, live=jnp.ones((1,), bool)))
    total = 0
    for j, tok in enumerate(np.asarray(toks[0])):
        _, kp, vp, load = step(params, kp, vp, jnp.asarray([j], jnp.int32),
                               jnp.asarray([tok], jnp.int32))
        total = total + np.asarray(load)
    return total


@pytest.mark.parametrize("scope", ["embed", "qkv", "rope", "kv_write",
                                   "paged_attention", "attn_out", "mlp",
                                   "moe", "moe_router", "moe_experts",
                                   "moe_shared", "unembed", "sampler"])
def test_the_compiled_mixed_step_names_its_phases(scope):
    """Read as the benchmark reads them: the program's table from
    instruction to path, through `trace_scopes.scopes_of`. The accepted
    readers' names are the other families'; `moe` and the three inside
    it are new, and `mlp` stays the dense layer's."""
    from benchmark import trace_scopes
    from paddle_tpu import telemetry
    cfg = _cfg()
    eng = _engine(cfg, mw.init_params(cfg))
    eng.warmup()
    table = telemetry.device_op_names()
    mixed = [m for m in table if m.startswith("jit_generation_mixed")]
    stacks = [trace_scopes.scopes_of(p)[0] for p in table[mixed[-1]].values()]
    assert any(scope in s for s in stacks), scope
    if scope.startswith("moe_"):
        assert all("moe" in s and s.index("moe") < s.index(scope)
                   for s in stacks if scope in s)
    if scope == "mlp":
        assert not any("moe" in s for s in stacks if "mlp" in s)


def _lowered_mixed(cfg):
    eng = _engine(cfg, mw.init_params(cfg))
    t, m = eng.token_budget, eng.max_blocks_per_seq
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct

    def mixed(params, kp, vp, tables, positions, tokens):
        return cfg.forward_paged(params, kp, vp, tables, positions, tokens,
                                 live=tables[:, 0] != 0)
    return jax.jit(mixed).lower(
        jax.tree.map(lambda a: sds(a.shape, a.dtype), eng.params),
        sds(eng.k_pools.shape, f32), sds(eng.v_pools.shape, f32),
        sds((t, m), i32), sds((t,), i32), sds((t,), i32)).as_text()


def test_the_lowered_step_does_not_grow_with_depth():
    base = _lowered_mixed(_cfg(_source(sparse=3)))
    txt = _lowered_mixed(_cfg(_source(sparse=6)))
    # the same operations, line for line: only constants differ (the
    # trip counts and the stacked shapes)
    assert len(txt.splitlines()) == len(base.splitlines())
    assert abs(len(txt) - len(base)) < 0.01 * len(base)
    # one loop for the leading dense layer, one for the sparse layers
    assert base.count("stablehlo.while") == 2


# ---------------------------------------------------------------------------
# the config: the source's keys, what is refused, the seam
# ---------------------------------------------------------------------------

def test_from_source_reads_the_benchmarks_file():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = json.load(open(os.path.join(root, "benchmark", "configs",
                                      "k_exaone_236b.json")))
    cfg = ExpertDecoderConfig.from_source(
        src, src["engine"]["max_context"],
        (src["experts_held"]["first"], src["experts_held"]["count"]))
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.kv_heads,
            cfg.head_dim) == (6144, 64, 8, 128)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (18432,
                                                                  2048)
    # the router keeps its published width; 16 experts are held
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held,
            cfg.num_experts_per_tok) == (128, 0, 16, 8)
    assert cfg.sliding_windows == (128, 128, 128, 0, 128)
    assert (cfg.dense_layers, cfg.sparse_layers) == (1, 4)
    assert (cfg.kv_layers, cfg.kv_row, cfg.max_seq_len) == (5, 1024, 1280)
    assert cfg.step_stats_len == 4 * 16
    assert cfg.rope_theta == 1e6 and cfg.routed_scaling_factor == 2.5
    # 7.42 GB of bfloat16 weights, as the issue's arithmetic has it
    n = sum(int(np.prod(s)) for s, _ in mw.leaf_shapes(cfg).values())
    assert abs(2 * n / 1e9 - 7.42) < 0.01
    # every field rides the programs' fingerprint
    meta = cfg.meta()
    assert meta["family"] == "moe_window"
    assert meta["sliding_windows"] == [128, 128, 128, 0, 128]
    json.dumps(meta)


@pytest.mark.parametrize("what,match", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"n_group": 4}, "grouped routing"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mlp_layer_types": ["sparse", "dense", "sparse", "sparse"]},
     "dense layer after"),
    ({"sliding_windows": [8, 8, 8, 8]}, "sliding_windows")])
def test_what_the_family_cannot_take_is_refused_loudly(what, match):
    with pytest.raises(ValueError, match=match):
        _cfg(_source(**what))


def test_the_family_is_not_imported_with_the_package():
    """`import paddle_tpu.generation` does not pay for a family no cell
    of the process builds (set-up time of the accepted cells)."""
    import subprocess
    import sys
    code = ("import sys, paddle_tpu.generation; "
            "print('paddle_tpu.generation.moe_window' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.stdout.strip() == "False", out.stderr[-500:]
