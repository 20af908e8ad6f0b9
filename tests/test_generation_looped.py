"""The LOOPED decoder family through the generation engine
(generation/looped.py; docs/generation.md, "Model families"): a stack
of layers run several times a token, a KV pool of passes x layers.

On XLA:CPU at tiny widths (3 layers x 2 and x 4 passes, a head that is
not hidden / heads): the paged forward against the full-context one,
the engine's streams against the O(N^2) oracle, the program's logits
against the benchmark's plain reference (and three planted faults seen
to differ), the pools' geometry, donation and copy-on-write, the size
of the lowered step against depth, and bfloat16 serving against a
float8 control.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import ouro_2_6b as R
from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                   GenerationRequest, KVCacheManager,
                                   NaiveGenerator, SamplingParams,
                                   init_params)
from paddle_tpu.generation import looped
from paddle_tpu.generation.looped import LoopedDecoderConfig
from paddle_tpu.monitor import gauge_get, stat_get


def _cfg(passes=2, layers=3, **kw):
    kw.setdefault("max_seq_len", 48)
    return LoopedDecoderConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=layers,
        num_attention_heads=4, num_key_value_heads=4, head_dim=12,
        intermediate_size=80, total_ut_steps=passes, **kw)


PASSES = pytest.mark.parametrize("passes", [2, 4])


def _engine(cfg, params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("decode_width", 4)
    kw.setdefault("prefill_chunk", 8)
    return GenerationEngine(cfg, params, **kw)


def _reqs(n=5, new=6):
    sps = [SamplingParams(), SamplingParams(temperature=0.8, seed=101),
           SamplingParams(temperature=0.9, top_k=8, seed=202)]
    return [GenerationRequest(prompt=list(range(3 + i, 14 + 3 * i)),
                              max_new_tokens=new, request_id=i,
                              sampling=sps[i % len(sps)])
            for i in range(n)]


def _source(cfg):
    """The config as the benchmark's reference reads it: a dict of the
    published keys."""
    return {k: getattr(cfg, k) for k in R.KEYS}


# ---------------------------------------------------------------------------
# paged against full-context
# ---------------------------------------------------------------------------

# What XLA:CPU gives here: a matmul's rows are NOT bitwise independent
# of how many rows ride with them in this jax (the GPT family's bitwise
# pins tests/test_generation.py::test_paged_decode_bitwise_parity_every_step
# and tests/test_kernels.py::test_chunked_prefill_mixed_batch_bitwise_
# vs_forward_full read a few ulps for the same reason), so the paged
# logits are held to the full-context ones within float32 rounding
# carried through passes x layers: 2e-5 on logits of order 1.
_PAGED_ATOL = 2e-5


@PASSES
def test_paged_prefill_chunks_then_decode_equal_forward_full(passes):
    cfg = _cfg(passes)
    params = looped.init_params(cfg, seed=1)
    bs, m, nblk, chunk = 4, 12, 40, 5
    lanes = m * bs
    rng = np.random.default_rng(0)
    lens = np.array([11, 7, 14])
    toks = rng.integers(0, cfg.vocab_size, (3, 24)).astype(np.int32)
    ff = jax.jit(lambda p, t, l: cfg.forward_full(p, t, l,
                                                  attn_lanes=lanes))
    step = jax.jit(cfg.forward_paged)
    mgr = KVCacheManager(nblk, bs)
    shape = (cfg.kv_layers, nblk, bs, cfg.kv_row)
    kp, vp = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    tables = np.zeros((3, m), np.int32)
    for i in range(3):
        mgr.alloc(i, mgr.blocks_for_tokens(24))
        tables[i] = mgr.table(i, m)
    # the prompts, streamed in ragged chunks of up to 5 slots a row
    done = np.zeros(3, int)
    logits_at = {}
    while (done < lens).any():
        rows, pos, tok, owner = [], [], [], []
        for i in range(3):
            for j in range(done[i], min(done[i] + chunk, lens[i])):
                rows.append(tables[i]); pos.append(j)
                tok.append(toks[i, j]); owner.append(i)
        pad = 15 - len(rows)            # one compiled shape
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
    # then single-token decode through the cache
    cl = lens.copy()
    for _ in range(4):
        nxt = np.array([toks[i, cl[i]] for i in range(3)], np.int32)
        lg, kp, vp = step(params, kp, vp,
                          jnp.asarray(np.concatenate(
                              [tables, np.zeros((12, m), np.int32)])),
                          jnp.asarray(np.concatenate([cl, np.zeros(12)]),
                                      jnp.int32),
                          jnp.asarray(np.concatenate([nxt, np.zeros(12)]),
                                      jnp.int32))
        cl = cl + 1
        oracle, kc, vc = ff(params, jnp.asarray(toks), jnp.asarray(cl))
        worst = max(worst, np.abs(np.asarray(lg[:3])
                                  - np.asarray(oracle)).max())
    assert worst <= _PAGED_ATOL, worst
    # the cache the paged path built IS the full-context one, slot by
    # slot: pass t's layer l at kv layer t * layers + l
    assert kc.shape == (cfg.kv_layers, 3, 24, 4, 12)
    for i in range(3):
        for p in (0, int(cl[i]) - 1):
            got = np.asarray(kp[:, tables[i][p // bs], p % bs])
            want = np.asarray(kc[:, i, p]).reshape(cfg.kv_layers, -1)
            assert np.abs(got - want).max() <= _PAGED_ATOL


@PASSES
@pytest.mark.parametrize("mode", ["chunked", "pallas"])
def test_engine_streams_equal_the_naive_generators(passes, mode):
    cfg = _cfg(passes)
    params = looped.init_params(cfg, seed=2)
    kw = {"chunked": {}, "pallas": {"kernel": "pallas"}}[mode]
    eng = _engine(cfg, params, **kw)
    naive = NaiveGenerator(cfg, params, attn_lanes=eng.attn_lanes)
    reqs = _reqs()
    got = {r.request_id: r.tokens for r in eng.generate(reqs)}
    for r in reqs:
        assert got[r.request_id] == naive.generate(r).tokens, r.request_id


# ---------------------------------------------------------------------------
# against the benchmark's plain reference
# ---------------------------------------------------------------------------

# float32 on both sides (the reference at precision "highest", which on
# the CPU is what the program computes too): rounding alone, through
# passes x layers. The planted faults read above 1e-2.
_REF_ATOL = 5e-5


def _program_logits(cfg, params, toks):
    """[T, V]: the program's logits at every position of one row."""
    t = len(toks)
    rows = jnp.asarray(np.tile(toks, (t, 1)), jnp.int32)
    out, _, _ = jax.jit(cfg.forward_full)(
        params, rows, jnp.arange(1, t + 1, dtype=jnp.int32))
    return np.asarray(out)


def _reference_logits(cfg, params, toks, fault=None):
    out = jax.jit(lambda p, t: R.forward(p, _source(cfg), t, 0, len(toks),
                                         "float32", fault=fault))(
        params, jnp.asarray(toks, jnp.int32))
    return np.asarray(out)


@PASSES
def test_program_logits_equal_the_benchmark_references(passes):
    cfg = _cfg(passes)
    # the benchmark's own weights, under the names the engine reads
    params = R.make_weights(_source(cfg), 7, dtype=jnp.float32)
    assert {k: v.shape for k, v in params.items()} == \
        {k: v.shape for k, v in looped.init_params(cfg).items()}
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 20)
    got = _program_logits(cfg, params, toks)
    want = _reference_logits(cfg, params, toks)
    assert np.abs(got - want).max() <= _REF_ATOL
    assert np.abs(want).max() > 0.5         # logits have a scale


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_fault_planted_in_the_reference_is_seen(fault):
    """The norm between passes, or a sandwich norm, taken out of one
    side: the two no longer agree."""
    cfg = _cfg(2)
    params = R.make_weights(_source(cfg), 7, dtype=jnp.float32)
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 20)
    got = _program_logits(cfg, params, toks)
    bad = _reference_logits(cfg, params, toks, fault=fault)
    assert np.abs(got - bad).max() > 200 * _REF_ATOL


def test_passes_sharing_one_cache_are_seen():
    """The per-pass cache taken out of the program: every pass writes
    and reads the cache layers of pass 0, so a decode step attends
    keys that a later pass overwrote. The streams leave the oracle's
    and the paged logits leave the reference's."""
    @dataclasses.dataclass(frozen=True)
    class Shared(LoopedDecoderConfig):
        def cache_slot(self, t, layer):
            return layer
    cfg, bad = _cfg(2), Shared(**dataclasses.asdict(_cfg(2)))
    params = R.make_weights(_source(cfg), 7, dtype=jnp.float32)
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 20)
    want = _reference_logits(cfg, params, toks)

    def paged(c):
        """Logits at every position, one token a step through the
        cache."""
        shape = (c.kv_layers, 8, 4, c.kv_row)
        kp = vp = jnp.zeros(shape, jnp.float32)
        table = jnp.asarray([[1, 2, 3, 4, 5, 0]], jnp.int32)
        step, out = jax.jit(c.forward_paged), []
        for j, tok in enumerate(toks):
            lg, kp, vp = step(params, kp, vp, table,
                              jnp.asarray([j], jnp.int32),
                              jnp.asarray([tok], jnp.int32))
            out.append(np.asarray(lg[0]))
        return np.stack(out)
    assert np.abs(paged(cfg) - want).max() <= _REF_ATOL
    assert np.abs(paged(bad) - want).max() > 200 * _REF_ATOL


# ---------------------------------------------------------------------------
# the pools: geometry from the config, donated, cloned by `cow`
# ---------------------------------------------------------------------------

@PASSES
@pytest.mark.parametrize("kv", ["fp32", "bf16", "int8"])
def test_pools_take_their_geometry_from_the_config(passes, kv):
    cfg = _cfg(passes)
    eng = _engine(cfg, looped.init_params(cfg), kv_dtype=kv,
                  prefix_cache=True)
    specs = eng._pool_specs()
    dt = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}[kv]
    assert specs["k_pools"][:2] == ((passes * 3, 64, 4, 4 * 12), dt)
    assert specs["v_pools"][:2] == specs["k_pools"][:2]
    assert ("k_scales" in specs) == (kv == "int8")
    if kv == "int8":
        assert specs["k_scales"][0] == (passes * 3, 64, 4, 4)
    # one source for the geometry: bytes a sequence, and the gauges
    per_tok = 2 * passes * 3 * (48 * jnp.dtype(dt).itemsize
                                + (4 * 4 if kv == "int8" else 0))
    assert eng.kv_bytes_per_seq() == per_tok * 4 * eng.max_blocks_per_seq
    assert gauge_get("GAUGE_kv_bytes_per_seq") == eng.kv_bytes_per_seq()
    assert gauge_get("GAUGE_kv_layers") == passes * 3
    # donated and alive after a step; what the engine held is dead
    eng.warmup()
    held = {n: getattr(eng, n) for n in specs}
    for r in _reqs(3):
        eng.submit(r)
    eng.step()
    for n, (shape, dtype, _) in specs.items():
        assert held[n].is_deleted(), n
        now = getattr(eng, n)
        assert not now.is_deleted() and now.shape == shape \
            and now.dtype == dtype, n
    # `cow` clones a block in every one of them, every cache layer
    marks = {n: jnp.arange(np.prod(getattr(eng, n).shape), dtype=jnp.float32
                           ).reshape(getattr(eng, n).shape)
             .astype(getattr(eng, n).dtype) for n in specs}
    for n, a in marks.items():
        setattr(eng, n, a)
    want = {n: np.asarray(a[:, 5].astype(jnp.float32))
            for n, a in marks.items()}
    eng._copy_block(5, 9)
    for n in specs:
        now = getattr(eng, n)
        assert np.array_equal(np.asarray(now[:, 9].astype(jnp.float32)),
                              want[n]), n
        assert np.array_equal(np.asarray(now[:, 5].astype(jnp.float32)),
                              want[n]), n


_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+\[[\d,]*\])\S* ([\w\-]+)\(")


def test_compiled_step_aliases_every_pool_and_copies_none():
    """As tests/test_kv_pools_in_place.py holds the GPT family: with
    the pools as a loop's carry, the compiled `mixed` and `cow` still
    alias every pool to its output, and nothing pool-shaped is made but
    by an in-place update (the loops' own carries are tuples). Float32
    pools here: XLA:CPU has no bfloat16 scatter and converts a bfloat16
    pool to float32 and back, which the TPU does not
    (tests/test_chip_compile.py compiles the bfloat16 step for a v5e
    and holds the same there)."""
    cfg = _cfg(2)
    eng = _engine(cfg, looped.init_params(cfg), prefix_cache=True)
    eng.warmup()
    dt = "f32"
    dims = [str(d) for d in eng.k_pools.shape]
    shapes = {"%s[%s]" % (dt, ",".join(d))
              for d in (dims, ["1"] + dims[1:], dims[1:])}
    for kind in ("mixed", "cow"):
        txt = eng._fns[kind]._compiled.as_text()
        head = txt.splitlines()[0]
        alias = head[head.index("input_output_alias"):]
        alias = alias[:alias.index("}, entry_computation_layout")]
        assert len(re.findall(r"\(\d+, \{\}", alias)) == 2, alias
        seen = {}
        for line in txt.splitlines():
            m = _INSTR.match(line)
            if m and m.group(1) in shapes:
                seen[m.group(2)] = seen.get(m.group(2), 0) + 1
        assert set(seen) <= {"parameter", "fusion", "scatter",
                             "dynamic-update-slice", "get-tuple-element"}, \
            (kind, seen)
        assert seen.get("scatter", 0) + seen.get("dynamic-update-slice",
                                                 0) > 0, (kind, seen)


# ---------------------------------------------------------------------------
# one loop body, whatever the depth
# ---------------------------------------------------------------------------

def _lowered_mixed(cfg):
    eng = _engine(cfg, looped.init_params(cfg))
    t, m, sw = eng.token_budget, eng.max_blocks_per_seq, eng.sample_width
    i32, f32 = jnp.int32, jnp.float32

    def mixed(params, kp, vp, tables, positions, tokens):
        return cfg.forward_paged(params, kp, vp, tables, positions, tokens)
    sds = jax.ShapeDtypeStruct
    return jax.jit(mixed).lower(
        jax.tree.map(lambda a: sds(a.shape, a.dtype), eng.params),
        sds(eng.k_pools.shape, f32), sds(eng.v_pools.shape, f32),
        sds((t, m), i32), sds((t,), i32), sds((t,), i32)).as_text()


def test_the_lowered_step_does_not_grow_with_depth_or_passes():
    base = _lowered_mixed(_cfg(passes=2, layers=3))
    for other in (_cfg(passes=2, layers=6), _cfg(passes=4, layers=3)):
        txt = _lowered_mixed(other)
        # the same operations, line for line: only constants differ (the
        # trip counts and the stacked shapes)
        assert len(txt.splitlines()) == len(base.splitlines())
        assert abs(len(txt) - len(base)) < 0.01 * len(base)
    # one layer body: one softmax's worth of exponentials a head, not
    # layers x passes of them
    assert base.count("stablehlo.while") == 2
    # (the GPT family's unrolled step does grow: the contrast)
    def gpt(layers):
        c = DecoderConfig(vocab_size=96, hidden=32, layers=layers, heads=4,
                          max_seq_len=48)
        p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         init_params(c))
        s = jax.ShapeDtypeStruct
        return jax.jit(c.forward_paged).lower(
            p, s((layers, 64, 4, 32), jnp.float32),
            s((layers, 64, 4, 32), jnp.float32), s((12, 12), jnp.int32),
            s((12,), jnp.int32), s((12,), jnp.int32)).as_text()
    assert len(gpt(4)) > 1.5 * len(gpt(2))


# ---------------------------------------------------------------------------
# bfloat16 weights and pool
# ---------------------------------------------------------------------------

def _served(cfg, weights, kv, prompts, new):
    eng = _engine(cfg, weights, kv_dtype=kv)
    return [eng.generate([GenerationRequest(prompt=list(p),
                                            max_new_tokens=new)])[0].tokens
            for p in prompts]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_bf16_serves_within_tolerance_and_fp8_falls_outside(seed):
    """Weights rounded to bfloat16 once and handed to both sides; the
    engine serves them with a bfloat16 pool, the reference upcasts
    them. Read as the benchmark reads it: the widest (reference's best
    logit - reference's logit of the served token) over 4 requests of
    28 greedy tokens. At these widths the bfloat16 engine reads up to
    0.018 (seeds 11 to 14), the float8 control (the reference with both
    operands of every matmul in float8, judged by ITS first choices)
    0.70 to 1.35: the tolerance is 0.05, and the control must pass five
    times that."""
    cfg, new = _cfg(2), 28
    w16 = R.make_weights(_source(cfg), seed)
    assert all(v.dtype == jnp.bfloat16 for v in w16.values())
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, 16) for _ in range(4)]
    ref = R.Reference(_source(cfg), pad_to=16 + new, new_tokens=new)

    def widest(served, control=None):
        return max(float(ref.gaps(w16, p, s, control=control).max())
                   for p, s in zip(prompts, served))
    served = _served(cfg, w16, "bf16", prompts, new)
    assert widest(served) <= 0.05
    assert widest(served, control="fp8") > 0.25
    # the float32 engine on the same (bfloat16-valued) weights serves
    # the reference's first choice but for rounding
    w32 = {k: v.astype(jnp.float32) for k, v in w16.items()}
    assert widest(_served(cfg, w32, "fp32", prompts, new)) <= 1e-4


# ---------------------------------------------------------------------------
# the seam, the fingerprint, the errors, the counter
# ---------------------------------------------------------------------------

def test_meta_carries_every_field_that_changes_the_program():
    cfg = _cfg(2)
    assert cfg.meta()["family"] == "looped"
    assert DecoderConfig().meta()["family"] == "gpt"
    for field, other in (("total_ut_steps", 4), ("head_dim", 16),
                         ("rope_theta", 1e4), ("rms_norm_eps", 1e-5),
                         ("intermediate_size", 96), ("max_seq_len", 32),
                         ("num_hidden_layers", 4)):
        assert dataclasses.replace(cfg, **{field: other}).meta() \
            != cfg.meta(), field
    for field, other in (("mlp_ratio", 2), ("heads", 2), ("layers", 3)):
        assert dataclasses.replace(DecoderConfig(), **{field: other}
                                   ).meta() != DecoderConfig().meta()


def test_from_source_reads_the_published_keys():
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "ouro_2_6b.json")
    src = json.load(open(path))
    cfg = LoopedDecoderConfig.from_source(
        src, max_context=src["engine"]["max_context"])
    assert (cfg.kv_layers, cfg.kv_row, cfg.max_seq_len) == (192, 2048, 512)
    assert cfg.max_position_embeddings == 65536
    shapes = looped.leaf_shapes(cfg)
    n = sum(int(np.prod(s)) for s, _ in shapes.values())
    # the published model: 2,667,974,657 parameters with the idle gate
    assert n == 2_667_974_657


@pytest.mark.parametrize("what", ["kv_dtype", "weight_quant", "gqa",
                                  "odd_head", "cap"])
def test_what_the_family_cannot_take_is_refused_loudly(what):
    cfg = _cfg(2)
    params = looped.init_params(cfg)
    if what == "kv_dtype":
        with pytest.raises(ValueError, match=r"auto\|fp32\|bf16\|int8\|fp8"):
            _engine(cfg, params, kv_dtype="fp16")
    elif what == "weight_quant":
        with pytest.raises(ValueError, match="LoopedDecoderConfig"):
            _engine(cfg, params, quant_mode="int8")
    elif what == "gqa":
        with pytest.raises(ValueError, match="grouped"):
            dataclasses.replace(cfg, num_key_value_heads=2)
    elif what == "odd_head":
        with pytest.raises(ValueError, match="even"):
            dataclasses.replace(cfg, head_dim=11)
    else:
        with pytest.raises(ValueError, match="context cap"):
            dataclasses.replace(cfg, max_seq_len=65537)


@pytest.mark.parametrize("family", ["gpt", "looped"])
def test_a_prefill_chunk_below_one_is_refused(family):
    """There is one engine, the mixed step's: `prefill_chunk=0` once
    chose a second one (bucketed prefill + decode), and is refused."""
    if family == "gpt":
        cfg = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                            max_seq_len=32)
        params = init_params(cfg, seed=0)
    else:
        cfg = _cfg(2)
        params = looped.init_params(cfg)
    with pytest.raises(ValueError, match="two-phase engine"):
        _engine(cfg, params, prefill_chunk=0)


def test_the_context_cap_is_the_engines_not_the_models():
    cfg = _cfg(2, max_seq_len=24)
    eng = _engine(cfg, looped.init_params(cfg))
    assert cfg.max_position_embeddings == 65536
    assert eng.max_blocks_per_seq == 6 and eng.attn_lanes == 24
    with pytest.raises(ValueError, match="max_seq_len 24"):
        eng.submit(GenerationRequest(prompt=list(range(20)),
                                     max_new_tokens=6))


def test_a_closed_pool_lets_go_of_the_engine_and_its_device_state():
    """`GenerationPool` set `engine.on_request_error` to a method of its
    own: a cycle, which kept 13.4 GB of weights and pools on the chip
    until a garbage collection (the benchmark's reference then found
    no room: my chip run, PR 29). `close()` takes it back."""
    import gc
    import weakref
    from paddle_tpu.generation import GenerationPool
    cfg = _cfg(2)
    eng = _engine(cfg, looped.init_params(cfg))
    pool = GenerationPool(eng)
    fut = pool.submit(GenerationRequest(prompt=[1, 2, 3], max_new_tokens=2))
    assert len(fut.result(timeout=120).tokens) == 2
    seen = weakref.ref(eng), weakref.ref(eng.k_pools)
    gc.disable()
    try:
        pool.close()
        del pool, eng, fut
        assert seen[0]() is None and seen[1]() is None
    finally:
        gc.enable()


def test_attended_tokens_counts_the_positions_the_live_slots_see():
    cfg = _cfg(2)
    eng = _engine(cfg, looped.init_params(cfg), prefix_cache=False)
    assert eng.kv.block_size == 4
    eng.submit(GenerationRequest(prompt=list(range(5)), max_new_tokens=3))
    a0 = stat_get("STAT_generation_attended_tokens")
    b0 = stat_get("STAT_generation_attended_blocks")
    eng.step()      # the 5 prompt tokens: positions 0..4 see 1..5 keys
    assert stat_get("STAT_generation_attended_tokens") - a0 == 15
    # ... in blocks of 4: positions 0..3 span one block, position 4 two
    assert stat_get("STAT_generation_attended_blocks") - b0 == 6
    eng.step()      # one decode slot at position 5
    assert stat_get("STAT_generation_attended_tokens") - a0 == 15 + 6
    assert stat_get("STAT_generation_attended_blocks") - b0 == 6 + 2


# ---------------------------------------------------------------------------
# the program's names on the device trace
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scope", ["embed", "loop_pass", "qkv", "rope",
                                   "kv_write", "paged_attention",
                                   "attn_out", "mlp", "unembed", "sampler"])
def test_the_compiled_mixed_step_names_its_phases(scope):
    """The accepted readers find the GPT family's names in the new
    family too; `loop_pass` (around a pass's layers) and `rope` (inside
    `qkv`) are new. Read as the benchmark reads them: the program's
    table from instruction to path, through `trace_scopes.scopes_of`."""
    from benchmark import trace_scopes
    from paddle_tpu import telemetry
    cfg = _cfg(2)
    eng = _engine(cfg, looped.init_params(cfg))
    eng.warmup()
    table = telemetry.device_op_names()
    mixed = [m for m in table if m.startswith("jit_generation_mixed")]
    stacks = [trace_scopes.scopes_of(p)[0] for p in table[mixed[-1]].values()]
    assert any(scope in s for s in stacks), scope
    inside = {"qkv", "rope", "kv_write", "paged_attention", "attn_out", "mlp"}
    if scope in inside:
        # a layer's phases lie inside the pass (`kv_write` also names
        # the slots' block and offset, computed once before the loop)
        assert any("loop_pass" in s and s.index("loop_pass")
                   < s.index(scope) for s in stacks if scope in s)
    if scope == "rope":
        assert all("qkv" in s for s in stacks if "rope" in s)
