"""The LATENT-attention expert family through the generation engine
(generation/mla_moe.py, kernels/latent_attention.py; docs/generation.md,
"Model families"): multi-head latent attention in its absorbed form
over ONE paged pool of latent rows, YaRN rotary, the routed experts of
which this program holds a share.

On XLA:CPU at tiny widths: the kernel's two forms against a dense loop
at the block edges, the absorbed attention against the expanded one on
the same rows, the YaRN tables against the formula written out here,
the paged forward against the full-context one and against the
benchmark's plain reference (and planted faults seen to differ), the
shares adding up to the uncut layer, copy-on-write and the prefix
cache over the one pool, the counters, the scopes of the compiled
step, the step's size against depth, and what `from_source` refuses.
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import kimi_k2_6 as R
from paddle_tpu.generation import (GenerationEngine, GenerationRequest,
                                   KVCacheManager, NaiveGenerator,
                                   SamplingParams)
from paddle_tpu.generation import mla_moe as ml
from paddle_tpu.generation import moe_window as mw
from paddle_tpu.generation.mla_moe import LatentDecoderConfig
from paddle_tpu.kernels import latent_attention as la
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.monitor import stat_get

FORMS = pytest.mark.parametrize("form", ["reference", "pallas"])
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _source(sparse=3, held=(4, 8), **kw):
    """A toy `config.json` in the source's keys, cut as the benchmark's
    file is: `n_routed_experts` the experts held, the router's width
    beside it; the published YaRN group."""
    src = {
        "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 1 + sparse,
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
        "intermediate_size": 80, "moe_intermediate_size": 16,
        "n_routed_experts": held[1], "n_routed_experts_published": 16,
        "experts_held": {"first": held[0], "count": held[1]},
        "num_experts_per_tok": 4, "n_shared_experts": 1,
        "routed_scaling_factor": 2.827, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "topk_method": "noaux_tc", "hidden_act": "silu",
        "rms_norm_eps": 1e-5, "first_k_dense_replace": 1,
        "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
        "rope_theta": 50000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "max_position_embeddings": 262144}
    src.update(kw)
    return src


def _cfg(src=None, max_context=48):
    src = src or _source()
    held = src["experts_held"]
    return LatentDecoderConfig.from_source(src, max_context,
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
# the kernel: both forms against a dense loop
# ---------------------------------------------------------------------------

def _dense_latent(q, pool, tables, visible, layer, sm_scale, width):
    """A loop in numpy: slot b's heads over the first `visible[b]` rows of
    its table, the context over the rows' first `width` lanes."""
    b, h, _ = q.shape
    out = np.zeros((b, h, width))
    for i in range(b):
        rows = np.asarray(pool[layer, tables[i]]).reshape(
            -1, pool.shape[-1])[:int(visible[i])]
        s = np.asarray(q[i]) @ rows.T * sm_scale          # [H, n]
        p = np.exp(s - s.max(axis=1, keepdims=True))
        out[i] = (p / p.sum(axis=1, keepdims=True)) @ rows[:, :width]
    return out


def _latent_case(bs=4, entries=48, slots=10, r=24, heads=4, seed=0):
    rng = np.random.default_rng(seed)
    n = slots * entries + 1
    pool = jnp.asarray(rng.normal(size=(3, n, bs, r)), jnp.float32)
    tables = rng.permutation(np.arange(1, n)).reshape(slots, entries)
    q = jnp.asarray(rng.normal(size=(slots, heads, r)), jnp.float32)
    return q, pool, tables.astype(np.int32)


def _tiled_case(case):
    """Sixteen slots of 128 heads: at most 512 query rows make Q = 4.
    Tables are each slot's own, a run's slots given one lane's."""
    q, pool, tables = _latent_case(slots=16, heads=128)
    visible = [37] * 16

    def lane(first, n, at):
        tables[first:first + n] = tables[first]
        visible[first:first + n] = range(at, at + n)
    if case == "chunk_runs":
        # three decode slots, a run of 7 from slot 3, one of 5 from the
        # first position, a decode slot
        visible[:3] = [37, 150, 9]
        lane(3, 7, 60)
        lane(10, 5, 1)
        visible[15] = 192
    elif case == "block_edge_run":
        # runs that start on a block's and a group's first position
        lane(0, 9, 129)
        lane(9, 5, 5)
        visible[14:] = [4, 128]
    elif case == "shared_prefix":
        # a prefix-cache hit: lane B's table shares lane A's first two
        # blocks; A's chunk ends at 10 positions where B's starts at 11
        lane(0, 5, 6)
        lane(5, 6, 11)
        tables[5:11, :2] = tables[0, :2]
        lane(11, 5, 100)
    elif case == "parked_between_runs":
        lane(0, 5, 20)
        lane(7, 6, 33)
        tables[[5, 6, 13, 15]] = 0
        for i in (5, 6, 13, 15):
            visible[i] = 1
    else:                               # "run_of_one"
        # one lane's slots that are not one position apart, a chunk's
        # last token beside decode slots
        tables[1] = tables[0]
        visible[:3] = [20, 22, 23]
        lane(4, 1, 9)
    return q, pool, tables, visible


@FORMS
@pytest.mark.parametrize("case", ["edges", "idle", "prefill_lane",
                                  "chunk_runs", "block_edge_run",
                                  "shared_prefix", "parked_between_runs",
                                  "run_of_one"])
def test_latent_kernel_against_a_dense_loop(form, case):
    """Blocks of 4 and 48 table entries, so the Pallas form's loop step
    is 32 blocks = 128 positions. `edges`: a context of one, ends at a
    block's edge and past it (4, 5), a group's edge and past it (128,
    129), two groups crossed (190). `idle`: parked slots on the trash
    block (block 0, one visible position) between live ones. A
    `prefill_lane`: six slots share one lane's table at consecutive
    positions, as the mixed step gives a chunk's tokens. The rest
    (`_tiled_case`) at 128 heads, where a tile holds 4 slots: runs
    longer than a tile and not a multiple of it, from an odd slot and
    from a block's edge, two lanes that share leading blocks at
    consecutive positions, parked slots between runs, runs of one."""
    if case in ("edges", "idle", "prefill_lane"):
        q, pool, tables = _latent_case()
        if case == "edges":
            visible = [1, 4, 5, 127, 128, 129, 130, 160, 190, 192]
        elif case == "idle":
            tables[1::2] = 0
            visible = [1, 1, 33, 1, 130, 1, 7, 1, 192, 1]
        else:
            tables[:6] = tables[0]
            visible = [125, 126, 127, 128, 129, 130, 3, 64, 65, 1]
    else:
        assert la.slots_per_tile(128, 4, 24 * 4, 48, 16, 16) == 4
        q, pool, tables, visible = _tiled_case(case)
    visible = jnp.asarray(visible, jnp.int32)
    with pa.kernel_form(form):
        got = jax.jit(lambda lyr: la.latent_attention(
            q, pool, jnp.asarray(tables), visible, sm_scale=0.3, layer=lyr,
            value_width=16))(jnp.int32(2))
    want = _dense_latent(q, pool, tables, visible, 2, 0.3, 16)
    assert got.shape == q.shape[:2] + (16,)
    assert np.abs(np.asarray(got) - want).max() <= 2e-6


def test_the_kernels_step_follows_its_fast_memory():
    # the published row (640 lanes of bfloat16) in blocks of 16: 32
    # blocks, 512 positions, a loop step; fewer where the table is narrower
    assert la.blocks_per_step(16, 1280, 640) == 32
    assert la.blocks_per_step(16, 1280, 20) == 16
    assert la.blocks_per_step(4, 96, 48) == 32
    # a tile: 8 slots of 64 heads at the published widths (512 query
    # rows); 4 where rows twice as wide leave the scores less room; 4 at
    # 128 heads; never more than the step's slots
    assert la.slots_per_tile(64, 16, 1280, 640, 512, 352) == 8
    assert la.slots_per_tile(64, 16, 5120, 640, 1024, 352) == 4
    assert la.slots_per_tile(128, 4, 512, 48, 16, 16) == 4
    assert la.slots_per_tile(4, 4, 96, 48, 16, 10) == 8


def test_the_tiles_follow_each_lanes_runs():
    """Lane A's table, lane B's (A's first two blocks, then its own), the
    trash table. A slot continues a run where the table row is the
    same and it sees one position more; runs cut into tiles of 2."""
    a, b_, trash = [1, 2, 3], [1, 2, 4], [0, 0, 0]
    tables = jnp.asarray([a, b_, b_, b_, b_, b_, trash, trash, a, a],
                         jnp.int32)
    visible = jnp.asarray([5, 6, 7, 8, 9, 10, 1, 1, 7, 8], jnp.int32)
    first, count = jax.jit(la.latent_tiles, static_argnums=2)(
        tables, visible, 2)
    assert np.asarray(first).tolist() == [0, 1, 3, 5, 6, 7, 8, 10, 10, 10]
    assert np.asarray(count).tolist() == [1, 2, 2, 1, 1, 1, 2, 0, 0, 0]
    first, count = la.latent_tiles(tables, visible, 8)
    assert np.asarray(count).tolist() == [1, 5, 1, 1, 2, 0, 0, 0, 0, 0]


def test_absorbed_equals_expanded_attention_on_the_same_rows():
    """The published form decompresses every head's keys and values from
    the latent rows (k_nope_h = W_uk,h^T c, v_h = W_uv,h^T c); the
    absorbed form pushes the query through W_uk and the context through
    W_uv. On the same rows they are one attention."""
    rng = np.random.default_rng(5)
    h, dn, dr, dv, kvl, n = 4, 8, 8, 8, 16, 37
    c = rng.normal(size=(n, kvl))
    k_rope = rng.normal(size=(n, dr))
    w_uk, w_uv = rng.normal(size=(kvl, h, dn)), rng.normal(size=(kvl, h, dv))
    q_nope, q_rope = rng.normal(size=(h, dn)), rng.normal(size=(h, dr))
    scale = 0.2
    # expanded: per head, the whole keys and values
    want = np.zeros((h, dv))
    for i in range(h):
        k = np.concatenate([c @ w_uk[:, i], k_rope], axis=1)
        s = k @ np.concatenate([q_nope[i], q_rope[i]]) * scale
        p = np.exp(s - s.max())
        want[i] = (p / p.sum()) @ (c @ w_uv[:, i])
    # absorbed, through the kernel's reference form on a pool of the rows
    r = 128
    rows = np.zeros((1, 16, 4, r))
    rows[0, 1:11].reshape(-1, r)[:n, :kvl] = c
    rows[0, 1:11].reshape(-1, r)[:n, kvl:kvl + dr] = k_rope
    q_lat = np.einsum("hd,chd->hc", q_nope, w_uk)
    q = np.zeros((1, h, r))
    q[0, :, :kvl], q[0, :, kvl:kvl + dr] = q_lat, q_rope
    ctx = la.latent_attention_reference(
        jnp.asarray(q, jnp.float32), jnp.asarray(rows, jnp.float32),
        jnp.arange(1, 11, dtype=jnp.int32)[None], jnp.asarray([n]), scale,
        0, kvl)
    got = np.einsum("hc,chd->hd", np.asarray(ctx[0], np.float64), w_uv)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def _yarn_by_hand(d, base, factor, orig, fast, slow):
    """DeepSeek-V3's published `yarn` rotary, written out: the correction
    dims of `fast` and `slow` rotations, a linear ramp between them over
    the d / 2 pairs, the interpolated frequency where the ramp is 1."""
    def corr(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    lo, hi = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), d - 1)
    out = []
    for i in range(d // 2):
        extra = base ** (-2 * i / d)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out.append(extra / factor * ramp + extra * (1 - ramp))
    return np.asarray(out), (lo, hi)


@pytest.mark.parametrize("which", ["toy", "published"])
def test_yarn_tables_against_the_formula_written_out(which):
    src = _source() if which == "toy" else json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "kimi_k2_6.json")))
    cfg = _cfg(src) if which == "toy" else LatentDecoderConfig.from_source(
        src, 10240, (0, 12))
    d = cfg.qk_rope_head_dim
    want, (lo, hi) = _yarn_by_hand(d, 50000.0, 64.0, 4096, 32.0, 1.0)
    assert (lo, hi) == ((1, 3) if which == "toy" else (8, 20))
    assert np.allclose(ml.yarn_inv_freq(cfg), want, rtol=1e-12)
    # the benchmark's reference, written apart, blends the same frequencies
    assert np.allclose(R.yarn_frequencies(R.sizes(src)), want, rtol=1e-12)
    # m = 0.1 ln(64) + 1; the softmax's scale (nope + rope)^-1/2 m^2; cos and
    # sin unscaled (mscale / mscale_all_dim = 1)
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.41589) < 1e-5
    assert cfg.softmax_scale == pytest.approx(
        (cfg.qk_nope_head_dim + d) ** -0.5 * m * m, rel=1e-12)
    pos = jnp.asarray([0, 1, 7, 4095, 9000], jnp.int32)
    cos, sin = ml._rope_tables(cfg, pos)
    # the angle is a float32 product, as the published code's is: at
    # position 9,000 its rounding alone moves sin by 1e-3
    ang = (np.asarray(pos, np.float32)[:, None]
           * want[None].astype(np.float32)).astype(np.float64)
    assert np.allclose(np.asarray(cos), np.cos(np.concatenate([ang, ang], 1)),
                       atol=2e-5)
    assert np.allclose(np.asarray(sin), np.sin(np.concatenate([ang, ang], 1)),
                       atol=2e-5)


def test_the_rotary_turns_interleaved_pairs():
    """Lanes (2i, 2i + 1) turn together by pair i's angle: after the
    program's rotary the scores of a query and a key depend on their
    positions' difference alone, and the pairs it turns are the
    interleaved ones (the rotate_half pairs give other scores)."""
    cfg = _cfg()
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 8)), jnp.float32)

    def score(i, j):
        ci, si = ml._rope_tables(cfg, jnp.asarray([i]))
        cj, sj = ml._rope_tables(cfg, jnp.asarray([j]))
        return float(jnp.sum(ml._rope(q[None], ci, si)
                             * ml._rope(k[None], cj, sj)))
    assert score(10, 3) == pytest.approx(score(107, 100), rel=1e-4)
    ang = 7 * ml.yarn_inv_freq(cfg)
    qn, kn = np.asarray(q[0], np.float64), np.asarray(k[0], np.float64)
    want = sum((qn[2 * i] * kn[2 * i] + qn[2 * i + 1] * kn[2 * i + 1])
               * math.cos(a) + (qn[2 * i] * kn[2 * i + 1]
                                - qn[2 * i + 1] * kn[2 * i]) * math.sin(a)
               for i, a in enumerate(ang))
    assert score(7, 0) == pytest.approx(want, rel=1e-4)


# ---------------------------------------------------------------------------
# paged against full-context, and the engine against the oracle
# ---------------------------------------------------------------------------

# float32 rounding through the layers (tests/test_generation_looped.py,
# _PAGED_ATOL); the absorbed and the expanded attention sum the same
# products in other orders
_PAGED_ATOL = 3e-5


@FORMS
def test_paged_prefill_chunks_then_decode_equal_forward_full(form):
    cfg = _cfg()
    params = ml.init_params(cfg, seed=1)
    bs, m, nblk, chunk = 4, 12, 40, 5
    lanes = m * bs
    rng = np.random.default_rng(0)
    lens = np.array([11, 7, 14])
    toks = rng.integers(0, cfg.vocab_size, (3, 24)).astype(np.int32)
    ff = jax.jit(lambda p, t, l: cfg.forward_full(p, t, l,
                                                  attn_lanes=lanes))
    with pa.kernel_form(form):
        step = jax.jit(cfg.forward_paged)
        mgr = KVCacheManager(nblk, bs)
        pool = jnp.zeros((cfg.kv_layers, nblk, bs, cfg.kv_row), jnp.float32)
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
            lg, pool = step(params, pool, jnp.asarray(np.stack(rows)),
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
            lg, pool = step(
                params, pool,
                jnp.asarray(np.concatenate(
                    [tables, np.zeros((12, m), np.int32)])),
                jnp.asarray(np.concatenate([cl, np.zeros(12)]), jnp.int32),
                jnp.asarray(np.concatenate([nxt, np.zeros(12)]),
                            jnp.int32))
            cl = cl + 1
            oracle, rows_full, values = ff(params, jnp.asarray(toks),
                                           jnp.asarray(cl))
            worst = max(worst, np.abs(np.asarray(lg[:3])
                                      - np.asarray(oracle)).max())
    assert worst <= _PAGED_ATOL, worst
    # the pool holds ONE row a position a layer, the full-context forward's:
    # [c | k_rope | zeros], 16 + 8 values in 128 lanes; the values are its
    # first kv_lora_rank columns
    assert cfg.kv_row == 128
    assert rows_full.shape == (cfg.kv_layers, 3, 24, 128)
    assert values.shape == (cfg.kv_layers, 3, 24, 16)
    for i in range(3):
        for p in (0, int(cl[i]) - 1):
            got = np.asarray(pool[:, tables[i][p // bs], p % bs])
            want = np.asarray(rows_full[:, i, p])
            assert np.abs(got - want).max() <= _PAGED_ATOL
            assert not got[:, 24:].any()


@FORMS
def test_engine_streams_equal_the_naive_generators(form):
    """The engine's absorbed step against the NaiveGenerator, which runs
    the published (expanded) form over the whole context every token."""
    cfg = _cfg()
    params = ml.init_params(cfg, seed=2)
    eng = _engine(cfg, params, kernel=form)
    naive = NaiveGenerator(cfg, params, attn_lanes=eng.attn_lanes)
    reqs = _reqs()
    got = {r.request_id: r.tokens for r in eng.generate(reqs)}
    for r in reqs:
        assert got[r.request_id] == naive.generate(r).tokens, r.request_id


# ---------------------------------------------------------------------------
# against the benchmark's plain reference
# ---------------------------------------------------------------------------

# float32 on both sides (the reference at precision "highest", which on the
# CPU is what the program computes too): rounding alone through four layers.
# The planted faults read above 1e-2.
_REF_ATOL = 5e-5


def _paged_logits(cfg, params, toks, form="reference", chunk=5):
    """[T, V]: the program's logits at every position of one row, through
    the paged cache: the first half of the prompt in chunks of `chunk`
    slots, then a token a step."""
    bs, m = 4, 12
    pool = jnp.zeros((cfg.kv_layers, 16, bs, cfg.kv_row), jnp.float32)
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
            lg, pool = step(params, pool, jnp.asarray(rows),
                            jnp.asarray(pos), jnp.asarray(tok))
            out.extend(np.asarray(lg[:n]))
            at += n
    return np.stack(out)


def _reference_logits(src, params, toks, variant=None):
    z = R.sizes(src)
    out = jax.jit(lambda p, t: R.forward(p, z, t, 0, len(toks), variant))(
        params, jnp.asarray(toks, jnp.int32))
    return np.asarray(out)


@FORMS
def test_program_logits_equal_the_benchmark_references(form):
    src = _source()
    cfg = _cfg(src)
    # the benchmark's own weights, under the names the engine reads
    params = R.make_weights(src, 7, dtype=jnp.float32)
    assert {k: v.shape for k, v in params.items()} == \
        {k: v.shape for k, v in ml.init_params(cfg).items()}
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


def test_the_reference_is_blocked_and_the_same():
    """At 512 positions the reference attends two blocks of 256 queries a
    layer; its logits are those of one block of 512 (the blocks change the
    order of nothing that is summed)."""
    src = _source(vocab_size=64)
    params = R.make_weights(src, 3, dtype=jnp.float32)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, 512),
                       jnp.int32)
    z = R.sizes(src)
    blocked = jax.jit(lambda p, t: R.forward(p, z, t, 500, 8))(params, toks)
    whole = R.BLOCK
    try:
        R.BLOCK = 512
        one = jax.jit(lambda p, t: R.forward(p, z, t, 500, 8))(params, toks)
    finally:
        R.BLOCK = whole
    assert np.abs(np.asarray(blocked) - np.asarray(one)).max() <= 1e-5


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_fault_planted_in_the_reference_is_seen(fault):
    """Pairs of rotate_half for the interleaved ones, YaRN's blend taken
    out, its m^2 dropped, the latent's norm dropped, the choice bias
    dropped, `routed_scaling_factor` dropped: each bends one side, and the
    two no longer agree."""
    src = _source()
    cfg = _cfg(src)
    params = R.make_weights(src, 7, dtype=jnp.float32)
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 24)
    got = _paged_logits(cfg, params, toks)
    bad = _reference_logits(src, params, toks, variant=fault)
    assert np.abs(got - bad).max() > 200 * _REF_ATOL, fault


# ---------------------------------------------------------------------------
# the chip's share
# ---------------------------------------------------------------------------

def _sparse_layer(params, at, first=None, held=None):
    w = {n: params[n][at] for n in ("router", "router_bias", "s_gu",
                                    "s_down", "e_gu", "e_down")}
    if first is not None:
        w["e_gu"] = w["e_gu"][first:first + held]
        w["e_down"] = w["e_down"][first:first + held]
    return w


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """The guide's share test at this family's config: a layer with all 16
    experts held, by the reference; then four shares of 4 experts each, by
    the PROGRAM (`moe` with a LatentDecoderConfig, told which experts it
    holds): the shares' routed parts and the shared expert counted once are
    the uncut layer."""
    whole_src = _source(held=(0, 16))
    params = R.make_weights(whole_src, 5, dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(20, 32)),
                    jnp.float32)
    want = np.asarray(R.whole_layer(whole_src, _sparse_layer(params, 1), x))
    shared = np.asarray(R.whole_layer(
        whole_src, _sparse_layer(params, 1, 0, 0), x, held=0))
    total = np.zeros_like(want)
    for share in range(4):
        src = _source(held=(4 * share, 4))
        cfg = _cfg(src)
        w = _sparse_layer(params, 1, 4 * share, 4)
        w.update(e_gu=w["e_gu"][None], e_down=w["e_down"][None], at=0)
        out, load = jax.jit(lambda w, x: mw.moe(
            cfg, w, x, jnp.ones((20,), bool)))(w, x)
        assert int(np.asarray(load).sum()) <= 20 * 4
        total += np.asarray(out) - shared
        ref = R.whole_layer(src, _sparse_layer(params, 1, 4 * share, 4), x,
                            first=4 * share, held=4)
        assert np.abs(np.asarray(out) - np.asarray(ref)).max() <= 1e-5
    assert np.abs(total + shared - want).max() <= 2e-5
    assert np.abs(want - shared).max() > 0.1


# ---------------------------------------------------------------------------
# the engine's one pool: copy-on-write, the prefix cache, what is refused
# ---------------------------------------------------------------------------

@FORMS
def test_copy_on_write_and_the_prefix_cache_over_one_latent_pool(form):
    """The family declares ONE pool and the engine holds it alone: no K or
    V pool, the bytes are the latent pool's. Two requests of one prompt of
    three whole blocks: the second attaches the first's published blocks,
    re-runs the last prompt token into a shared block through the
    copy-on-write program, and both streams are those of an engine with no
    prefix cache."""
    cfg = _cfg()
    params = ml.init_params(cfg, seed=3)
    eng = _engine(cfg, params, kernel=form, prefix_cache=True, lookahead=0)
    assert list(eng._pool_specs()) == ["latent_pools"]
    assert eng.k_pools is None and eng.v_pools is None
    assert eng.latent_pools.shape == (4, 64, 4, 128)
    assert eng.kv_pool_bytes() == eng.latent_pools.nbytes == 4 * 64 * 4 * \
        128 * 4
    assert eng.kv_bytes_per_seq() == 4 * 4 * 128 * 4 * eng.max_blocks_per_seq
    assert eng._program_pools("cow") == ("latent_pools",)
    eng.warmup()
    prompt = list(range(5, 17))                  # 12 tokens: three blocks
    reqs = [GenerationRequest(prompt=prompt, max_new_tokens=6,
                              request_id=i) for i in range(2)]
    hits0 = stat_get("STAT_generation_prefix_hits")
    cow0 = stat_get("STAT_generation_prefix_cow_copies")
    out = {}
    eng.submit(reqs[0])
    # the first request's prompt streams in and is published, block by block
    while eng._lane_seq[0] is None or \
            eng._lane_seq[0].prefilled < len(prompt):
        out.update((r.request_id, r.tokens) for r in eng.step())
    eng.submit(reqs[1])
    while not eng.idle:
        out.update((r.request_id, r.tokens) for r in eng.step())
    assert stat_get("STAT_generation_prefix_hits") > hits0
    assert stat_get("STAT_generation_prefix_cow_copies") > cow0
    plain = _engine(cfg, params, kernel=form, prefix_cache=False)
    want = {r.request_id: r.tokens for r in plain.generate(
        [GenerationRequest(prompt=prompt, max_new_tokens=6, request_id=i)
         for i in range(2)])}
    assert out == want and out[0] == out[1]


def test_a_quantized_pool_is_refused_for_the_latent_family():
    cfg = _cfg()
    with pytest.raises(ValueError, match="quantized pool needs a K and"):
        _engine(cfg, ml.init_params(cfg), kv_dtype="int8")


def test_the_counters_against_a_hand_count():
    """One request of 11 prompt tokens and 3 new ones through four layers
    that all see the whole context: the attended positions are summed over
    them; the routing counts are the program's own router's."""
    cfg = _cfg()
    params = ml.init_params(cfg, seed=4)
    eng = _engine(cfg, params, prefix_cache=False, prefill_chunk=16,
                  lookahead=0)
    names = ("STAT_generation_attended_tokens", "STAT_generation_moe_pairs",
             "STAT_generation_moe_experts_touched",
             "STAT_generation_context_rows")
    before = [stat_get(n) for n in names]
    eng.submit(GenerationRequest(prompt=list(range(20, 31)),
                                 max_new_tokens=3))
    eng.step()          # the 11 prompt tokens, positions 0..10
    got = [stat_get(n) - b for n, b in zip(names, before)]
    assert got[0] == 4 * sum(p + 1 for p in range(11))
    # 11 tokens x 4 choices over three sparse layers, of which 8 of 16
    # experts are held: some pairs land here, never more than all of them
    assert 0 < got[1] <= 3 * 11 * 4
    assert 0 < got[2] <= 3 * 8
    # the lane's 11 rows once a layer
    assert got[3] == 4 * 11


def test_the_tile_counters_against_a_hand_count():
    """128 heads make the kernel's tile 4 slots. Two prompts of 3 and 2
    tokens, then their two decode slots beside a chunk of 11 prompt
    tokens: d + ceil(k / 4) tiles of live slots, the chunk's k slots
    attended in tiles of more than one (parked slots are neither)."""
    cfg = _cfg(_source(num_attention_heads=128))
    eng = _engine(cfg, ml.init_params(cfg, seed=4), prefix_cache=False,
                  prefill_chunk=16, lookahead=0)
    assert la.slots_per_tile(
        128, eng.kv.block_size,
        cfg.kv_row * eng.latent_pools.dtype.itemsize,
        eng.max_blocks_per_seq, cfg.kv_lora_rank, eng.token_budget) == 4
    assert cfg.step_stats_len == 3 * 8 + 2
    names = ("STAT_generation_latent_tiles",
             "STAT_generation_latent_shared_slots")

    def step():
        before = [stat_get(n) for n in names]
        eng.step()
        return [stat_get(n) - b for n, b in zip(names, before)]
    for prompt in (list(range(3)), list(range(10, 12))):
        eng.submit(GenerationRequest(prompt=prompt, max_new_tokens=4))
    assert step() == [1 + 1, 3 + 2]
    eng.submit(GenerationRequest(prompt=list(range(20, 31)),
                                 max_new_tokens=2))
    assert step() == [2 + 3, 11]
    assert step() == [3, 0]             # three decode slots, each alone


def test_a_prefill_chunk_needs_its_lanes_rows_once():
    """The latent roofline's bytes come from the rows each LANE sees, once
    however many of its slots attend them: a chunk of 16 prompt tokens
    needs one pass over its lane's rows, not one a token. A decoding lane
    beside it adds its own context once."""
    cfg = _cfg()
    src = _source()
    eng = _engine(cfg, ml.init_params(cfg, seed=4), prefix_cache=False,
                  prefill_chunk=16, lookahead=0)
    names = ("STAT_generation_attended_tokens", "STAT_generation_context_rows")

    def step():
        before = [stat_get(n) for n in names]
        eng.step()
        return [stat_get(n) - b for n, b in zip(names, before)]
    eng.submit(GenerationRequest(prompt=list(range(40)), max_new_tokens=2))
    attended, rows = step()          # positions 0..15
    assert (attended, rows) == (4 * sum(range(1, 17)), 4 * 16)
    attended, rows = step()          # positions 16..31
    assert (attended, rows) == (4 * sum(range(17, 33)), 4 * 32)
    one_pass = 4 * 32 * (16 + 8) * R.LATENT_BYTES
    assert R.latent_bytes(src, rows) == one_pass
    assert R.latent_bytes(src, attended) > 8 * one_pass
    eng.step()                       # positions 32..39: the prompt ends
    eng.submit(GenerationRequest(prompt=list(range(20)), max_new_tokens=2))
    attended, rows = step()          # a decode slot at 40 and 0..15
    assert (attended, rows) == (4 * (41 + sum(range(1, 17))),
                                4 * (41 + 16))


@pytest.mark.parametrize("scope", ["embed", "latent_q", "latent_kv",
                                   "kv_write", "latent_absorb",
                                   "latent_attention", "latent_out",
                                   "attn_out", "mlp", "moe", "moe_router",
                                   "moe_experts", "moe_shared", "unembed",
                                   "sampler"])
def test_the_compiled_mixed_step_names_its_phases(scope):
    """Read as the benchmark reads them: the program's table from
    instruction to path, through `trace_scopes.scopes_of`."""
    from benchmark import trace_scopes
    from paddle_tpu import telemetry
    cfg = _cfg()
    eng = _engine(cfg, ml.init_params(cfg))
    eng.warmup()
    table = telemetry.device_op_names()
    mixed = [m for m in table if m.startswith("jit_generation_mixed")]
    stacks = [trace_scopes.scopes_of(p)[0] for p in table[mixed[-1]].values()]
    assert any(scope in s for s in stacks), scope
    if scope in ("latent_attention", "latent_absorb", "latent_out"):
        # none inside another's
        others = {"latent_attention", "latent_absorb", "latent_out"} - {scope}
        assert not any(others & set(s) for s in stacks if scope in s)


def _lowered_mixed(cfg):
    eng = _engine(cfg, ml.init_params(cfg))
    t, m = eng.token_budget, eng.max_blocks_per_seq
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct

    def mixed(params, pool, tables, positions, tokens):
        return cfg.forward_paged(params, pool, tables, positions, tokens,
                                 live=tables[:, 0] != 0)
    return jax.jit(mixed).lower(
        jax.tree.map(lambda a: sds(a.shape, a.dtype), eng.params),
        sds(eng.latent_pools.shape, f32),
        sds((t, m), i32), sds((t,), i32), sds((t,), i32)).as_text()


def test_the_lowered_step_does_not_grow_with_depth():
    base = _lowered_mixed(_cfg(_source(sparse=3)))
    txt = _lowered_mixed(_cfg(_source(sparse=6)))
    assert len(txt.splitlines()) == len(base.splitlines())
    assert abs(len(txt) - len(base)) < 0.01 * len(base)
    assert base.count("stablehlo.while") == 2


# ---------------------------------------------------------------------------
# the config: the source's keys, what is refused
# ---------------------------------------------------------------------------

def test_from_source_reads_the_benchmarks_file():
    src = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "kimi_k2_6.json")))
    cfg = LatentDecoderConfig.from_source(
        src, src["engine"]["max_context"],
        (src["experts_held"]["first"], src["experts_held"]["count"]))
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank) == (7168, 64, 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (18432,
                                                                  2048)
    # the router keeps its published width; 12 experts are held
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held,
            cfg.num_experts_per_tok) == (384, 0, 12, 8)
    assert (cfg.dense_layers, cfg.sparse_layers) == (1, 4)
    # one row of 512 + 64 a position, in 640 lanes
    assert (cfg.kv_layers, cfg.kv_row, cfg.max_seq_len) == (5, 640, 10240)
    assert cfg.kv_windows == (0,) * 5
    # the held experts' loads, then the latent kernel's two counts
    assert cfg.step_stats_len == 4 * 12 + 2
    assert cfg.routed_scaling_factor == 2.827 and cfg.rope_theta == 50000
    # 3,496,763,904 parameters, 6.99 GB of bfloat16, as the configuration's
    # `deployment` counts them
    n = sum(int(np.prod(s)) for s, _ in ml.leaf_shapes(cfg).values())
    assert n == 3_496_763_904
    meta = cfg.meta()
    assert meta["family"] == "mla_moe"
    json.dumps(meta)


@pytest.mark.parametrize("what,match", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"n_group": 8}, "n_group"),
    ({"topk_group": 4}, "topk_group"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"num_nextn_predict_layers": 1}, "multi-token prediction"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"q_lora_rank": None}, "low-rank"),
    ({"rope_scaling": {"type": "linear", "factor": 4}}, "rope_scaling")])
def test_what_the_family_cannot_take_is_refused_loudly(what, match):
    with pytest.raises(ValueError, match=match):
        _cfg(_source(**what))


def test_the_family_is_not_imported_with_the_package():
    import subprocess
    import sys
    code = ("import sys, paddle_tpu.generation; "
            "print('paddle_tpu.generation.mla_moe' in sys.modules, "
            "'paddle_tpu.kernels.latent_attention' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         cwd=ROOT)
    assert out.stdout.strip() == "False False", out.stderr[-500:]
