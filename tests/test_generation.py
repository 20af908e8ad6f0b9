"""Generation engine tests: paged KV cache, paged-step/full-context
logit parity, sampler determinism, continuous batching, backpressure, and
the zero-steady-state-recompile pin (docs/generation.md)."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation import (BlockPoolExhausted, DecoderConfig,
                                   GenerationEngine, GenerationPool,
                                   GenerationRequest, KVCacheManager,
                                   NaiveGenerator, SamplingParams,
                                   TRASH_BLOCK, forward_full,
                                   forward_paged, init_params,
                                   sample_tokens)
from paddle_tpu.generation.sampling import _sample_one
from paddle_tpu.kernels.paged_attention import (paged_attention_pallas,
                                                paged_attention_reference)
from paddle_tpu.monitor import gauge_get, stat_get
from paddle_tpu.serving import ServingQueueFull


CFG = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                    max_seq_len=32)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=0)


def _engine(params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("decode_width", 4)
    return GenerationEngine(CFG, params, **kw)


# ---------------------------------------------------------------------------
# KVCacheManager accounting
# ---------------------------------------------------------------------------

def test_kv_manager_alloc_free_accounting():
    mgr = KVCacheManager(num_blocks=8, block_size=4)
    assert mgr.free_blocks == 7  # block 0 reserved
    a = mgr.alloc("a", 3)
    assert len(a) == 3 and TRASH_BLOCK not in a
    assert mgr.free_blocks == 4 and mgr.used_blocks == 3
    b = mgr.alloc("b", 2)
    assert set(a).isdisjoint(b)
    # table pads with trash to the requested width
    t = mgr.table("a", 6)
    assert t[:3] == a and t[3:] == [TRASH_BLOCK] * 3
    mgr.extend("a")
    assert mgr.free_blocks == 1
    assert mgr.free("a") == 4
    assert mgr.free_blocks == 5
    # double-free is a no-op
    assert mgr.free("a") == 0
    assert mgr.free_blocks == 5


def test_kv_manager_exhaustion_and_eviction_counter():
    mgr = KVCacheManager(num_blocks=4, block_size=4)
    mgr.alloc("a", 3)
    with pytest.raises(BlockPoolExhausted):
        mgr.alloc("b", 1)
    with pytest.raises(BlockPoolExhausted):
        mgr.extend("a")
    ev0 = stat_get("STAT_generation_evictions")
    assert mgr.evict("a") == 3
    assert stat_get("STAT_generation_evictions") == ev0 + 1
    assert gauge_get("GAUGE_generation_blocks_free") == 3


def test_kv_manager_blocks_for_tokens():
    mgr = KVCacheManager(num_blocks=8, block_size=4)
    assert [mgr.blocks_for_tokens(n) for n in (1, 4, 5, 8, 9)] == \
        [1, 1, 2, 2, 3]


def test_kv_manager_freed_blocks_recycle():
    mgr = KVCacheManager(num_blocks=4, block_size=4)
    a = mgr.alloc("a", 3)
    mgr.free("a")
    b = mgr.alloc("b", 3)
    assert sorted(a) == sorted(b)


# ---------------------------------------------------------------------------
# prefill/decode parity
# ---------------------------------------------------------------------------

# The paged step and the full-context forward run the same float32
# operations over a key axis of the same width, yet XLA:CPU does not
# give them the same last bits: it picks a matmul's tiling from the
# batch's shape ([3, h] against [3, 16, h] here). Largest gap measured
# over 24 seeds of weights and tokens: 1.43e-6, on logits up to 4.2 (a
# few ULP). The limit is ten times that. A pool that holds bfloat16
# rows, the planted fault, reads 5.5e-3 to 1.4e-2 over the same seeds.
PARITY_ATOL = 1.5e-5


@pytest.mark.parametrize("fault", [None, "bf16_pool"])
def test_paged_decode_bitwise_parity_every_step(params, fault):
    """The acceptance gate: at EVERY decode step the paged single-token
    logits equal a full-context recompute of the same position within
    PARITY_ATOL (no longer bit for bit: see there). With the fault
    planted the same comparison reads ten times over the limit, so
    the limit is one a broken cache cannot pass."""
    bs, nblocks = 4, 32
    m = -(-CFG.max_seq_len // bs)
    lanes = m * bs
    rng = np.random.default_rng(1)
    lens = np.array([5, 9, 3], np.int32)
    sb = 16
    toks = np.zeros((3, sb), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = rng.integers(0, CFG.vocab_size, n)

    ff = jax.jit(lambda p, t, l: forward_full(CFG, p, t, l,
                                              attn_lanes=lanes))
    last, kc, vc = ff(params, jnp.asarray(toks), jnp.asarray(lens))

    mgr = KVCacheManager(nblocks, bs)
    kp = np.zeros((CFG.layers, nblocks, bs, CFG.heads, CFG.head_dim),
                  np.float32)
    vp = np.zeros_like(kp)
    tables = np.zeros((3, m), np.int32)
    for i, n in enumerate(lens):
        mgr.alloc(i, mgr.blocks_for_tokens(int(n)))
        tbl = mgr.table(i, m)
        tables[i] = tbl
        for pos in range(int(n)):
            kp[:, tbl[pos // bs], pos % bs] = np.asarray(kc)[:, i, pos]
            vp[:, tbl[pos // bs], pos % bs] = np.asarray(vc)[:, i, pos]

    dec = jax.jit(lambda p, k, v, t, c, x: forward_paged(
        CFG, p, k, v, t, c, x))
    pool_dtype = jnp.bfloat16 if fault else jnp.float32
    kpj, vpj = jnp.asarray(kp, pool_dtype), jnp.asarray(vp, pool_dtype)
    cur, cl = toks.copy(), lens.copy()
    nxt = np.asarray(jnp.argmax(last, -1), np.int32)
    gaps = []
    for step in range(6):
        for i in range(3):
            need = mgr.blocks_for_tokens(int(cl[i]) + 1)
            while len(mgr.owned(i)) < need:
                mgr.extend(i)
            tables[i] = mgr.table(i, m)
        logits, kpj, vpj = dec(params, kpj, vpj, jnp.asarray(tables),
                               jnp.asarray(cl), jnp.asarray(nxt))
        for i in range(3):
            cur[i, cl[i]] = nxt[i]
        cl = cl + 1
        oracle, _, _ = ff(params, jnp.asarray(cur), jnp.asarray(cl))
        if fault is None:
            np.testing.assert_allclose(
                logits, oracle, rtol=0, atol=PARITY_ATOL,
                err_msg="parity broke at step %d" % step)
        gaps.append(np.abs(np.asarray(logits - oracle)).max())
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
    if fault:
        assert max(gaps) >= 10 * PARITY_ATOL, gaps


def test_engine_tokens_match_naive_full_context(params):
    """End-to-end: engine token streams (mixed greedy + sampled) equal
    the naive full-context redecode oracle."""
    eng = _engine(params)
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(8):
        plen = int(rng.integers(2, 12))
        reqs.append(GenerationRequest(
            prompt=list(rng.integers(1, CFG.vocab_size, plen)),
            max_new_tokens=int(rng.integers(3, 8)),
            sampling=SamplingParams(
                temperature=0.8 if i % 2 else 0.0,
                top_k=8 if i % 3 == 0 else 0,
                top_p=0.9 if i % 4 == 0 else 1.0, seed=i),
            request_id=i))
    res = {r.request_id: r for r in eng.generate(list(reqs))}
    naive = NaiveGenerator(CFG, params, buckets="pow2:16",
                           attn_lanes=eng.attn_lanes)
    for r in reqs:
        assert naive.generate(r).tokens == res[r.request_id].tokens


def test_trash_block_lanes_do_not_perturb_active(params):
    """A lone sequence decodes identically whether its batch-mates'
    lanes are empty or mid-flight — lane isolation."""
    solo = _engine(params)
    req = GenerationRequest(prompt=[3, 1, 4, 1, 5], max_new_tokens=6,
                            sampling=SamplingParams(temperature=0.7,
                                                    seed=42),
                            request_id="solo")
    a = solo.generate([req]).pop().tokens
    crowd = _engine(params)
    others = [GenerationRequest(prompt=[i + 2] * 3, max_new_tokens=9,
                                request_id=i) for i in range(3)]
    b = {r.request_id: r for r in crowd.generate(
        others + [GenerationRequest(prompt=[3, 1, 4, 1, 5],
                                    max_new_tokens=6,
                                    sampling=SamplingParams(
                                        temperature=0.7, seed=42),
                                    request_id="solo")])}
    assert b["solo"].tokens == a


# ---------------------------------------------------------------------------
# sampler determinism
# ---------------------------------------------------------------------------

def test_sampler_deterministic_under_fixed_seed():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(3, 64)),
                         jnp.float32)
    args = (jnp.asarray([0.9] * 3, jnp.float32),
            jnp.asarray([10, 0, 5], jnp.int32),
            jnp.asarray([0.95, 1.0, 0.8], jnp.float32),
            jnp.asarray([1, 2, 3], jnp.int32),
            jnp.asarray([4, 4, 4], jnp.int32))
    a = np.asarray(sample_tokens(logits, *args))
    b = np.asarray(sample_tokens(logits, *args))
    assert np.array_equal(a, b)
    # different step (fold_in count) changes the draw for at least one
    # lane over a few steps; different seed likewise
    diff = [np.asarray(sample_tokens(
        logits, args[0], args[1], args[2], args[3],
        jnp.asarray([s] * 3, jnp.int32))) for s in range(5, 10)]
    assert any(not np.array_equal(a, d) for d in diff)


def test_sampler_greedy_and_filters():
    logits = jnp.asarray([[0.0, 5.0, 1.0, 4.0]], jnp.float32)
    greedy = sample_tokens(
        logits, jnp.asarray([0.0]), jnp.asarray([0]),
        jnp.asarray([1.0]), jnp.asarray([0]), jnp.asarray([0]))
    assert int(np.asarray(greedy)[0]) == 1
    # top_k=1 == greedy regardless of temperature/seed
    for seed in range(6):
        t = sample_tokens(
            logits, jnp.asarray([1.5]), jnp.asarray([1]),
            jnp.asarray([1.0]), jnp.asarray([seed]), jnp.asarray([7]))
        assert int(np.asarray(t)[0]) == 1
    # top_k=2: only the two best tokens ever appear
    draws = {int(np.asarray(sample_tokens(
        logits, jnp.asarray([2.0]), jnp.asarray([2]),
        jnp.asarray([1.0]), jnp.asarray([s]), jnp.asarray([0])))[0])
        for s in range(24)}
    assert draws <= {1, 3}
    # tiny top_p collapses to the argmax
    for seed in range(6):
        t = sample_tokens(
            logits, jnp.asarray([2.0]), jnp.asarray([0]),
            jnp.asarray([0.05]), jnp.asarray([seed]), jnp.asarray([3]))
        assert int(np.asarray(t)[0]) == 1


# the sampler's batch-level branch (PR 30): rows of (temperature,
# top_k, top_p); seeds and steps differ a row
_BRANCH_BATCHES = {
    "all_greedy": [(0.0, 0, 1.0)] * 5,
    "all_greedy_filters_set": [(0.0, 8, 0.9), (0.0, 3, 0.5),
                               (0.0, 0, 0.7), (0.0, 12, 1.0)],
    "all_sampled_filters_unset": [(0.8, 0, 1.0)] * 5,
    "all_sampled_filters_set": [(0.8, 8, 0.9), (1.3, 3, 0.5),
                                (0.6, 0, 0.7), (0.9, 12, 1.0)],
    "mixed_filters_unset": [(0.0, 0, 1.0), (0.8, 0, 1.0),
                            (0.0, 0, 1.0), (1.1, 0, 1.0)],
    "mixed_filters_set": [(0.0, 8, 0.9), (0.8, 8, 0.9), (0.0, 0, 1.0),
                          (1.2, 0, 0.6), (0.7, 5, 1.0)],
    "one_sampled_among_greedy": [(0.0, 0, 1.0)] * 6 + [(0.9, 0, 0.95)],
    "one_row_greedy": [(0.0, 0, 1.0)],
    "one_row_sampled": [(0.7, 4, 0.9)],
}


def _sampler_args(rows, seed0=11, step0=3):
    n = len(rows)
    return (jnp.asarray([r[0] for r in rows], jnp.float32),
            jnp.asarray([r[1] for r in rows], jnp.int32),
            jnp.asarray([r[2] for r in rows], jnp.float32),
            jnp.arange(seed0, seed0 + n, dtype=jnp.int32),
            jnp.arange(step0, step0 + n, dtype=jnp.int32))


@pytest.mark.parametrize("case", sorted(_BRANCH_BATCHES))
def test_sample_tokens_equals_the_vmapped_lanes(case):
    """`sample_tokens` decides ONE branch for the batch on the device;
    whichever it takes, every row's token is the one the lanes vmapped
    without the branch give (the parent's sampler, called directly)."""
    rows = _BRANCH_BATCHES[case]
    logits = jnp.asarray(np.random.default_rng(5).normal(
        size=(len(rows), 257)) * 3.0, jnp.float32)
    args = _sampler_args(rows)
    oracle = jax.jit(jax.vmap(_sample_one))(logits, *args)
    got = sample_tokens(logits, *args)
    assert got.dtype == jnp.int32 and got.shape == (len(rows),)
    assert np.array_equal(np.asarray(got), np.asarray(oracle))
    greedy = np.asarray([r[0] <= 0 for r in rows])
    assert np.array_equal(np.asarray(got)[greedy],
                          np.asarray(jnp.argmax(logits, -1))[greedy])


@pytest.mark.parametrize("row", [(0.0, 0, 1.0), (0.0, 6, 0.8),
                                 (0.8, 0, 1.0), (0.8, 6, 0.8)],
                         ids=["greedy", "greedy_filters_set",
                              "sampled", "sampled_filters_set"])
def test_row_token_whatever_its_batch_mates_ask_for(row):
    """Determinism contract across the branch: a row's token is a pure
    function of (its logits, seed, step). Its batch-mates flipping
    between greedy and sampled flips the BATCH's branch (for a greedy
    row) and must not move the row's token."""
    logits = jnp.asarray(np.random.default_rng(9).normal(
        size=(6, 129)) * 2.0, jnp.float32)
    seen = set()
    for mates in ([], [(0.0, 0, 1.0)] * 5, [(0.9, 0, 1.0)] * 5,
                  [(0.9, 4, 0.7)] * 5,
                  [(0.0, 0, 1.0), (1.0, 0, 1.0)] * 2 + [(0.0, 3, 0.5)]):
        rows = [row] + mates
        out = sample_tokens(logits[:len(rows)], *_sampler_args(rows))
        seen.add(int(np.asarray(out)[0]))
    assert len(seen) == 1


def _mixed_requests(rng, n, sampled):
    """n requests; `sampled(i)` says whether request i draws."""
    return [GenerationRequest(
        prompt=list(rng.integers(1, CFG.vocab_size,
                                 int(rng.integers(2, 12)))),
        max_new_tokens=int(rng.integers(3, 8)),
        sampling=SamplingParams(
            temperature=0.8 if sampled(i) else 0.0,
            top_k=8 if i % 3 == 0 else 0,
            top_p=0.9 if i % 4 == 0 else 1.0, seed=i),
        request_id=i) for i in range(n)]


_ENGINE_FORMS = {
    "chunked_ahead": dict(prefill_chunk=3),
    "chunked": dict(prefill_chunk=3, lookahead=0),
    "speculative": dict(prefill_chunk=3, spec_tokens=2),
}


@pytest.mark.parametrize("form", sorted(_ENGINE_FORMS))
def test_engine_streams_across_the_sampler_branch_match_naive(
        params, form):
    """A request list that is part greedy, part sampled, so that steps
    of both branches (and lanes that change sides as requests come and
    go) make up every stream: each equals the naive oracle's, which
    samples one row at a time."""
    reqs = _mixed_requests(np.random.default_rng(23), 9,
                           lambda i: i % 3 == 1)
    eng = _engine(params, **_ENGINE_FORMS[form])
    res = {r.request_id: r.tokens for r in eng.generate(
        [GenerationRequest(**r.__dict__) for r in reqs])}
    naive = NaiveGenerator(CFG, params, buckets="pow2:16",
                           attn_lanes=eng.attn_lanes)
    for r in reqs:
        assert naive.generate(r).tokens == res[r.request_id]


@pytest.mark.parametrize("traffic", ["greedy", "sampled", "mixed"])
@pytest.mark.parametrize("form", sorted(_ENGINE_FORMS))
def test_sampler_filter_steps_counts_the_steps_with_a_sampled_row(
        params, form, traffic):
    """STAT_generation_sampler_filter_steps: one for each step whose
    `temps`, as handed to the device, holds a value above 0: none in a
    greedy run, every step where every request draws."""
    eng = _engine(params, **_ENGINE_FORMS[form])
    handed = []                 # each step's temps, as the device got them
    run = eng._run

    def spy(kind, *rest):
        if kind == "mixed":
            handed.append(np.asarray(rest[-1])[:eng.sample_width])
        return run(kind, *rest)
    eng._run = spy
    reqs = _mixed_requests(
        np.random.default_rng(31), 7,
        {"greedy": lambda i: False, "sampled": lambda i: True,
         "mixed": lambda i: i in (2, 3)}[traffic])
    before = stat_get("STAT_generation_sampler_filter_steps")
    eng.generate(reqs)
    counted = stat_get("STAT_generation_sampler_filter_steps") - before
    assert len(handed) > 5
    assert counted == sum(bool((t > 0).any()) for t in handed)
    if traffic == "greedy":
        assert counted == 0
    elif traffic == "sampled":
        assert counted == len(handed)
    else:
        assert 0 < counted < len(handed)


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)


# ---------------------------------------------------------------------------
# continuous batching: join/leave, eviction replay, recompile pin
# ---------------------------------------------------------------------------

def test_continuous_join_leave_zero_recompiles(params):
    """The tentpole pin: after warmup, a mixed-length continuous
    stream (sequences joining and leaving mid-flight) triggers ZERO
    engine compilations — STAT_generation_compile stands still and the
    decode executable is reused for every step."""
    eng = _engine(params)
    eng.warmup()
    c0 = stat_get("STAT_generation_compile")
    rng = np.random.default_rng(3)
    reqs = [GenerationRequest(
        prompt=list(rng.integers(1, CFG.vocab_size,
                                 int(rng.integers(2, 13)))),
        max_new_tokens=int(rng.integers(2, 9)), request_id=i)
        for i in range(12)]  # 12 requests through 4 lanes => churn
    res = eng.generate(reqs)
    assert len(res) == 12
    assert {r.request_id for r in res} == set(range(12))
    assert stat_get("STAT_generation_compile") == c0
    # everything returned to the pool — except blocks the prefix cache
    # (default-on since PR 14) deliberately persists for reuse; those
    # are exactly its held set, and no sequence holds anything
    held = (eng.prefix_cache.held_blocks
            if eng.prefix_cache is not None else 0)
    assert eng.kv.used_blocks == held
    assert not eng.kv._tables


def test_eviction_replay_is_deterministic(params):
    """Pool pressure preempts the youngest sequence; its deterministic
    replay must yield the same tokens as an uncontended run."""
    small = GenerationEngine(CFG, params, num_blocks=10, block_size=4,
                             decode_width=4)
    reqs = [GenerationRequest(prompt=[i + 1] * 10, max_new_tokens=14,
                              sampling=SamplingParams(temperature=0.9,
                                                      seed=i),
                              request_id=i) for i in range(3)]
    ev0 = stat_get("STAT_generation_evictions")
    contended = {r.request_id: r.tokens for r in small.generate(
        [GenerationRequest(**r.__dict__) for r in reqs])}
    assert stat_get("STAT_generation_evictions") > ev0  # it did preempt
    big = _engine(params)
    relaxed = {r.request_id: r.tokens for r in big.generate(reqs)}
    assert contended == relaxed


def test_submit_validation_is_per_request(params):
    eng = _engine(params)
    with pytest.raises(ValueError):
        eng.submit(GenerationRequest(prompt=[], max_new_tokens=2))
    with pytest.raises(ValueError):
        eng.submit(GenerationRequest(prompt=[1] * 40, max_new_tokens=2))
    with pytest.raises(ValueError):
        eng.submit(GenerationRequest(prompt=[1], max_new_tokens=0))
    # a request larger than the whole pool can never run
    tiny = GenerationEngine(CFG, params, num_blocks=3, block_size=4,
                            decode_width=2)
    with pytest.raises(ValueError):
        tiny.submit(GenerationRequest(prompt=[1] * 10,
                                      max_new_tokens=10))
    # engine untouched by the rejects
    assert eng.pending_count == 0 and eng.active_count == 0


def test_eos_termination(params):
    eng = _engine(params)
    greedy = eng.generate([GenerationRequest(
        prompt=[3, 1, 4], max_new_tokens=10, request_id=0)])[0]
    assert len(greedy.tokens) == 10 and greedy.finish_reason == "length"
    eos = greedy.tokens[4]
    eng2 = _engine(params)
    res = eng2.generate([GenerationRequest(
        prompt=[3, 1, 4], max_new_tokens=10, eos_token=eos,
        request_id=0)])[0]
    assert res.finish_reason == "eos"
    assert res.tokens == greedy.tokens[:4]


# ---------------------------------------------------------------------------
# GenerationPool: scheduler semantics
# ---------------------------------------------------------------------------

def test_pool_concurrent_submitters_each_get_their_answer(params):
    eng = _engine(params)
    with GenerationPool(eng, queue_depth=64) as pool:
        oracle = {}
        naive = NaiveGenerator(CFG, params, buckets="pow2:16",
                               attn_lanes=eng.attn_lanes)
        outs = {}

        def worker(i):
            req = GenerationRequest(prompt=[i + 1, i + 2, i + 3],
                                    max_new_tokens=4 + (i % 3))
            outs[i] = pool.run(req, timeout=120).tokens

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(8):
            ref = naive.generate(GenerationRequest(
                prompt=[i + 1, i + 2, i + 3],
                max_new_tokens=4 + (i % 3))).tokens
            assert outs[i] == ref, "submitter %d got wrong stream" % i


def test_pool_per_request_error_isolation(params):
    eng = _engine(params)
    with GenerationPool(eng, queue_depth=16) as pool:
        good1 = pool.submit(GenerationRequest(prompt=[1, 2],
                                              max_new_tokens=3))
        bad = pool.submit(GenerationRequest(prompt=[1] * 40,
                                            max_new_tokens=3))
        good2 = pool.submit(GenerationRequest(prompt=[1, 2],
                                              max_new_tokens=3))
        with pytest.raises(ValueError):
            bad.result(timeout=60)
        a = good1.result(timeout=60)
        b = good2.result(timeout=60)
        assert a.tokens == b.tokens and a.finish_reason == "length"


def test_pool_backpressure_raises_queue_full(params):
    eng = _engine(params)
    # don't start the worker: the queue can only fill
    pool = GenerationPool(eng, queue_depth=2, _start=False)
    r0 = stat_get("STAT_generation_rejected")
    pool.submit(GenerationRequest(prompt=[1], max_new_tokens=1))
    pool.submit(GenerationRequest(prompt=[1], max_new_tokens=1))
    with pytest.raises(ServingQueueFull):
        pool.submit(GenerationRequest(prompt=[1], max_new_tokens=1),
                    timeout=0.05)
    assert stat_get("STAT_generation_rejected") == r0 + 1
    # closing errors the queued futures
    pool._closed = True
    with pool._lock:
        while pool._queue:
            _, fut = pool._queue.popleft()
            fut._set_error(RuntimeError("closed"))


def test_pool_close_drains(params):
    eng = _engine(params)
    pool = GenerationPool(eng, queue_depth=16)
    futs = [pool.submit(GenerationRequest(prompt=[1, 2, 3],
                                          max_new_tokens=4))
            for _ in range(5)]
    pool.close()
    for f in futs:
        assert f.result(timeout=1).finish_reason == "length"


# ---------------------------------------------------------------------------
# paged-attention kernel: reference vs pallas(interpret)
# ---------------------------------------------------------------------------

def test_paged_attention_pallas_matches_reference():
    rng = np.random.default_rng(0)
    b, h, d, bs, n, m = 3, 4, 8, 4, 16, 4
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n, bs, h, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n, bs, h, d)), jnp.float32)
    tbl = jnp.asarray(rng.integers(1, n, (b, m)), jnp.int32)
    ctx = jnp.asarray([5, 9, 3], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, tbl, ctx)
    pal = paged_attention_pallas(q, kp, vp, tbl, ctx)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               atol=2e-5, rtol=2e-5)


def test_paged_attention_kernel_pin_seam(params, monkeypatch):
    """The engine's `kernel=` pins the form its steps are traced in,
    and the form rides every step's compile key (`kern=`): pinned the
    other way the key misses, never a stale program. No flag names
    the form any more."""
    from paddle_tpu.core import program_accounting
    from paddle_tpu.flags import lowering_snapshot
    assert not [k for k, _ in lowering_snapshot() if "paged" in k]
    keys = {}
    real = program_accounting.accounted

    def accounted(jitted, avals, *, tag, key="", meta=None):
        keys[meta["kern"]] = key
        return real(jitted, avals, tag=tag, key=key, meta=meta)
    monkeypatch.setattr(program_accounting, "accounted", accounted)
    for form in ("reference", "pallas"):
        eng = _engine(params, kernel=form)
        assert eng.kernel == form
        eng._get_fn("cow")
    assert set(keys) == {"reference", "pallas"}
    assert keys["reference"] != keys["pallas"]
    assert _engine(params).kernel == "reference"      # XLA:CPU's own


def test_decode_width_one_matches_width_four(params):
    """Batch-width invariance of the decode step (the same property
    tests/test_serving.py pins for the Predictor)."""
    for w in (1, 4):
        eng = _engine(params, decode_width=w)
        res = eng.generate([GenerationRequest(
            prompt=[9, 8, 7], max_new_tokens=5, request_id=0)])[0]
        if w == 1:
            base = res.tokens
    assert res.tokens == base


# ---------------------------------------------------------------------------
# chunked prefill + mixed step (PR 10)
# ---------------------------------------------------------------------------

def test_chunked_streams_match_naive(params):
    """The chunked mixed step produces the SAME token streams as the
    naive full-recompute oracle: the sampler's step indices are the
    oracle's, and the paged logits lie within rounding of its."""
    rng = np.random.default_rng(11)
    reqs = [GenerationRequest(
        prompt=list(rng.integers(1, CFG.vocab_size,
                                 int(rng.integers(2, 14)))),
        max_new_tokens=int(rng.integers(3, 9)),
        sampling=SamplingParams(temperature=0.7 if i % 2 else 0.0,
                                seed=i),
        request_id=i) for i in range(6)]
    chunked = _engine(params, prefill_chunk=3)
    a = {r.request_id: r.tokens for r in chunked.generate(
        [GenerationRequest(**r.__dict__) for r in reqs])}
    naive = NaiveGenerator(CFG, params, buckets="pow2:16",
                           attn_lanes=chunked.attn_lanes)
    for r in reqs:
        assert naive.generate(r).tokens == a[r.request_id]


def test_decode_advances_during_chunked_prefill(params):
    """No head-of-line blocking: while a long prompt streams through
    chunked prefill, every already-decoding lane gains exactly one
    token per step (the acceptance pin)."""
    eng = _engine(params, decode_width=2, prefill_chunk=2)
    eng.submit(GenerationRequest(prompt=[3, 1, 4], max_new_tokens=20,
                                 request_id="A"))
    eng.step()  # admit + first chunk(s) of A
    a_seq = next(s for s in eng._lane_seq
                 if s is not None and s.req.request_id == "A")
    while not a_seq.generated:
        eng.step()  # finish A's prefill: A is now decoding
    eng.submit(GenerationRequest(prompt=[2] * 24, max_new_tokens=2,
                                 request_id="B"))
    eng.step()  # admits B; its 24-token prompt needs 12 chunked steps
    b_seq = next(s for s in eng._lane_seq
                 if s is not None and s.req.request_id == "B")
    assert b_seq.prefilled < len(b_seq.req.prompt)
    steps_during_prefill = 0
    while b_seq.prefilled < len(b_seq.req.prompt):
        before = len(a_seq.generated)
        eng.step()
        steps_during_prefill += 1
        assert len(a_seq.generated) == before + 1, \
            "decode lane stalled while B prefilled"
    assert steps_during_prefill >= 5  # B really was long


def test_pad_tokens_stat_emitted(params):
    """STAT_generation_pad_tokens: the engine pays for the unused
    slots of its mixed batch and emits the stat (satellite: pad waste
    is observable)."""
    p1 = stat_get("STAT_generation_pad_tokens")
    chunked = _engine(params, prefill_chunk=4)
    chunked.generate([GenerationRequest(prompt=[1] * 5,
                                        max_new_tokens=2,
                                        request_id=0)])
    # mixed steps with one lone sequence leave unused slots
    assert stat_get("STAT_generation_pad_tokens") > p1


def test_replayed_request_survives_admit_fault_and_keeps_priority(
        params):
    """Scheduler fairness regression (satellite): a transient fault on
    a REPLAYED request's re-admission (injected generation.kv_alloc
    raise) must neither kill the request nor let a never-started
    request overtake it."""
    from paddle_tpu import failpoints as fp
    eng = _engine(params)
    eng.submit(GenerationRequest(
        prompt=[5, 4, 3], max_new_tokens=8,
        sampling=SamplingParams(temperature=0.8, seed=9),
        request_id="A"))
    eng.step()
    for _ in range(3):
        eng.step()  # A decodes a few tokens
    assert eng._preempt_youngest()  # manufacture a replay of A
    assert eng._pending[0].req.request_id == "A"
    assert eng._pending[0].evictions == 1
    eng.submit(GenerationRequest(prompt=[7, 7], max_new_tokens=2,
                                 request_id="B"))  # never started
    r0 = stat_get("STAT_generation_replay_retries")
    e0 = stat_get("STAT_generation_errors")
    fp.arm_spec("generation.kv_alloc=raise@once")
    try:
        eng.step()  # re-admission faults: must NOT raise or kill A
    finally:
        fp.disarm("generation.kv_alloc")
    assert stat_get("STAT_generation_replay_retries") == r0 + 1
    assert stat_get("STAT_generation_errors") == e0
    # fairness: A still first in line, B did not overtake it
    assert [s.req.request_id for s in eng._pending] == ["A", "B"]
    out = {}
    while not eng.idle:
        for r in eng.step():
            out[r.request_id] = r.tokens
    # deterministic replay straight through the fault
    relaxed = _engine(params).generate([GenerationRequest(
        prompt=[5, 4, 3], max_new_tokens=8,
        sampling=SamplingParams(temperature=0.8, seed=9),
        request_id="A")])[0]
    assert out["A"] == relaxed.tokens


def test_preemption_replay_through_mid_prefill_chunk(params):
    """Eviction determinism extended to chunked prefill: preempting a
    sequence WHILE its prompt is mid-chunk-stream replays the whole
    prompt from scratch and regenerates the identical stream."""
    eng = _engine(params, prefill_chunk=2)
    req = GenerationRequest(prompt=[2] * 14, max_new_tokens=6,
                            sampling=SamplingParams(temperature=0.9,
                                                    seed=4),
                            request_id="A")
    eng.submit(GenerationRequest(**req.__dict__))
    eng.step()  # admitted, first chunk in
    seq = next(s for s in eng._lane_seq if s is not None)
    assert 0 < seq.prefilled < len(seq.req.prompt)  # mid-prefill
    assert eng._preempt_youngest()
    out = {}
    while not eng.idle:
        for r in eng.step():
            out[r.request_id] = r
    assert out["A"].evictions == 1
    relaxed = _engine(params).generate(
        [GenerationRequest(**req.__dict__)])[0]
    assert out["A"].tokens == relaxed.tokens


def test_token_budget_validation(params):
    with pytest.raises(ValueError):
        _engine(params, prefill_chunk=4, token_budget=2)  # < width 4
    eng = _engine(params, prefill_chunk=4, token_budget=0)
    assert eng.token_budget == eng.decode_width + 4


# ---------------------------------------------------------------------------
# acceptance bench (slow: runs the full bench.py generation block)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_generation_bench_acceptance(tmp_path, monkeypatch):
    """ISSUE-5 acceptance: paged decode >= 2x naive tokens/s on CPU,
    streams bitwise identical, zero steady-state recompiles."""
    import importlib.util
    import os
    monkeypatch.setenv("PT_GENERATION_BENCH_SNAPSHOT",
                       str(tmp_path / "gen_snap.json"))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "pt_bench", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    block = mod.bench_generation()
    assert block["tokens_bitwise_identical"] is True
    assert block["steady_state_recompiles"] == 0
    assert block["speedup_paged_vs_naive"] >= 2.0
    assert block["decode_step_p95_regressions"] == []


@pytest.mark.slow
def test_generation_prefix_bench_acceptance(tmp_path, monkeypatch):
    """ISSUE-14 acceptance (tentpole a): warm prefix cache >= 2x lower
    TTFT p95 than cold recompute of a shared system prompt, streams
    bitwise identical, zero steady-state recompiles."""
    import importlib.util
    import os
    monkeypatch.setenv("PT_GENERATION_PREFIX_BENCH_SNAPSHOT",
                       str(tmp_path / "gen_prefix_snap.json"))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "pt_bench", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    block = mod.bench_generation_prefix()
    assert block["tokens_bitwise_identical"] is True
    assert block["steady_state_recompiles"] == 0
    assert block["meets_ttft_2x"] is True
    assert block["cache_on"]["prefix_hits"] > 0
    assert block["cache_on"]["kv_blocks_saved"] > 0
    assert block["prefix_admit_p95_regressions"] == []


@pytest.mark.slow
def test_generation_spec_bench_acceptance(tmp_path, monkeypatch):
    """ISSUE-14 acceptance (tentpole b): speculative decoding's
    streams are bitwise plain decode, the drafter's proposals get
    accepted, and tokens/s does not regress (>= 1.0x honest ratio —
    the ngram draft is host-side, the verify slots ride the step the
    engine already pays for)."""
    import importlib.util
    import os
    monkeypatch.setenv("PT_GENERATION_SPEC_BENCH_SNAPSHOT",
                       str(tmp_path / "gen_spec_snap.json"))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "pt_bench", os.path.join(repo, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    block = mod.bench_generation_spec()
    assert block["tokens_bitwise_identical"] is True
    assert block["steady_state_recompiles"] == 0
    assert block["meets_1p0x"] is True
    assert block["accepted"] > 0
    assert block["mixed_step_p95_regressions"] == []
