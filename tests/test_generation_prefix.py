"""PR 14: cross-request prefix caching (copy-on-write KV block
sharing) + speculative decoding in the ragged mixed step.

Pins the two bitwise contracts of docs/generation.md:

- a request admitted through a cache hit emits the SAME stream, bit
  for bit, as the same request against a cold cache (keyed by
  request_id — only completion ORDER may change, MIGRATION.md);
- a speculative engine's accepted streams are bitwise-identical to
  plain decode across greedy / temperature / top-k / top-p.

Plus the refcount ledger (idempotent free extended to shared blocks),
COW divergence under concurrent sequences, LRU eviction + preemption
replay under an armed generation.kv_alloc failpoint, and the two new
failpoint sites' fallbacks (prefix_lookup -> cold prefill with an
unpoisoned cache, draft_step -> plain decode).

PR 37: the ledger's gauges are running counts. A recount oracle holds
them to the formulas they replaced after every call of a random walk,
a counted (not timed) test holds one mutation's cost independent of
the pool's size, and an engine run holds them through prefix hits,
copy-on-write and preemption.

PR 39: the cache publishes and matches whole blocks only, so a run
that shares nothing copies nothing; copy-on-write is reached through
an exact duplicate of a prompt whose length is a block multiple."""
import sys

import numpy as np
import pytest

from paddle_tpu import failpoints
from paddle_tpu.failpoints import InjectedFault
from paddle_tpu.generation import (BlockPoolExhausted, DecoderConfig,
                                   GenerationEngine, GenerationRequest,
                                   KVCacheManager, SamplingParams,
                                   TRASH_BLOCK, init_params)
from paddle_tpu.generation.kv_cache import PrefixCache
from paddle_tpu.monitor import gauge_get, stat_get

CFG = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                    max_seq_len=48)


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=0)


@pytest.fixture(autouse=True)
def _disarm_all():
    failpoints.disarm()
    yield
    failpoints.disarm()


def _engine(params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("decode_width", 4)
    kw.setdefault("prefill_chunk", 8)
    return GenerationEngine(CFG, params, **kw)


# a 16-token prefix = four whole blocks of 4; suffixes diverge after it
PREFIX = [7, 3, 11, 2, 9, 14, 5, 8, 21, 4, 13, 6, 17, 10, 1, 12]


def _shared_reqs(n=6):
    """Mixed sampling configs over one shared prefix: greedy,
    temperature, top-k, top-p lanes all in the same batch."""
    out = []
    for i in range(n):
        sp = [SamplingParams(),
              SamplingParams(temperature=0.8, seed=100 + i),
              SamplingParams(temperature=0.9, top_k=8, seed=200 + i),
              SamplingParams(temperature=0.7, top_p=0.9, seed=300 + i),
              ][i % 4]
        out.append(GenerationRequest(
            prompt=PREFIX + [40 + i, 41 + i, 42 + i],
            max_new_tokens=6, sampling=sp, request_id=i))
    return out


def _streams(eng, reqs, tolerate_faults=False):
    for r in reqs:
        eng.submit(r)
    out = {}
    while not eng.idle:
        try:
            for r in eng.step():
                out[r.request_id] = r.tokens
        except InjectedFault:
            if not tolerate_faults:
                raise
    return out


# ---------------------------------------------------------------------------
# KVCacheManager: refcounted sharing + idempotent free (satellite 1)
# ---------------------------------------------------------------------------

def test_kv_refcounted_free_is_idempotent_and_respects_sharing():
    mgr = KVCacheManager(num_blocks=8, block_size=4)
    a = mgr.alloc("a", 3)
    # b shares a's first two blocks and claims one private
    b = mgr.attach("b", a[:2], 1)
    assert b[:2] == a[:2] and b[2] not in a
    assert mgr.shared_blocks == 2 and mgr.blocks_saved == 2
    assert mgr.used_blocks == 4          # 3 + 1 private, sharing free
    assert mgr.free("a") == 1            # only a's unshared block back
    assert mgr.shared_blocks == 0        # b now sole owner
    # double-free decrements NOTHING a second time: the table is gone
    assert mgr.free("a") == 0
    assert mgr.refcount(a[0]) == 1 and mgr.refcount(a[1]) == 1
    # still-referenced blocks never re-entered the free list
    c = mgr.alloc("c", mgr.free_blocks)
    assert set(c).isdisjoint(mgr.owned("b"))
    mgr.free("c")
    assert mgr.free("b") == 3
    assert mgr.used_blocks == 0 and mgr.free_blocks == 7


def test_kv_cow_swaps_private_block_and_drops_reference():
    mgr = KVCacheManager(num_blocks=8, block_size=4)
    a = mgr.alloc("a", 2)
    mgr.attach("b", a, 0)                # pure shared attach
    old, new = mgr.cow("b", 1)
    assert old == a[1] and new not in a
    assert mgr.owned("b") == [a[0], new]
    assert mgr.refcount(old) == 1        # a's reference alone
    assert mgr.refcount(new) == 1
    # a private block refuses COW — nothing to diverge from
    with pytest.raises(ValueError):
        mgr.cow("b", 1)
    mgr.free("a")
    mgr.free("b")
    assert mgr.used_blocks == 0


def test_kv_attach_rejects_free_block_and_exhaustion_is_atomic():
    mgr = KVCacheManager(num_blocks=4, block_size=4)
    a = mgr.alloc("a", 2)
    with pytest.raises(ValueError):
        mgr.attach("b", [a[0], 99], 0)   # 99 is not a live block
    free0 = mgr.free_blocks
    with pytest.raises(BlockPoolExhausted):
        mgr.attach("b", a, 2)            # only 1 free
    assert mgr.free_blocks == free0      # nothing leaked
    assert mgr.refcount(a[0]) == 1       # shared refs not half-bumped


# ---------------------------------------------------------------------------
# prefix cache: bitwise identity, COW divergence, eviction (tentpole a)
# ---------------------------------------------------------------------------

def test_shared_prefix_streams_bitwise_identical_to_cold(params):
    """THE prefix-cache contract: cache-on streams equal cache-off
    streams keyed by request_id, on the first (cold) batch AND on a
    second batch served from the now-warm cache."""
    want = _streams(_engine(params, prefix_cache=False), _shared_reqs())
    eng = _engine(params)
    h0 = stat_get("STAT_generation_prefix_hits")
    assert _streams(eng, _shared_reqs()) == want
    hits_first = stat_get("STAT_generation_prefix_hits") - h0
    assert hits_first > 0                # later admits reuse the first
    # second batch on the SAME engine: every request hits
    m0 = stat_get("STAT_generation_prefix_misses")
    assert _streams(eng, _shared_reqs()) == want
    assert stat_get("STAT_generation_prefix_hits") - h0 > hits_first
    assert stat_get("STAT_generation_prefix_misses") == m0


def test_cow_divergence_under_concurrent_sequences(params):
    """Exact duplicates of an 8-token prompt on blocks of 4 (chunk 6,
    no divisor of the block): a consumer admitted after the producer
    published hits both blocks and re-runs the last prompt token INTO
    the last shared block, so it must copy-on-write while earlier
    lanes keep decoding — streams stay bitwise-identical to a
    no-sharing run."""
    reqs = [GenerationRequest(
        prompt=PREFIX[:8], max_new_tokens=3 + 2 * (i % 3),
        sampling=SamplingParams(temperature=0.85, seed=i),
        request_id=i) for i in range(6)]
    want = _streams(
        _engine(params, prefill_chunk=6, prefix_cache=False),
        [GenerationRequest(**r.__dict__) for r in reqs])
    c0 = stat_get("STAT_generation_prefix_cow_copies")
    eng = _engine(params, prefill_chunk=6)
    assert _streams(eng, reqs) == want
    assert stat_get("STAT_generation_prefix_cow_copies") > c0
    # divergence never corrupted the ledger: nothing still tabled
    assert not eng.kv._tables
    assert eng.kv.used_blocks == eng.prefix_cache.held_blocks


@pytest.mark.parametrize("chunk,block", [(6, 4), (8, 16), (24, 16)])
def test_keys_are_whole_blocks_only(params, chunk, block):
    """PR 39: the cache's unit is the pool's block, whatever the chunk.
    `keys_for` cuts at block multiples only (no partial tail), each
    key a pure function of the tokens before its boundary; a served
    prompt of no block multiple leaves only whole-block entries."""
    eng = _engine(params, prefill_chunk=chunk, block_size=block,
                  num_blocks=16, decode_width=2)
    pc = eng.prefix_cache
    prompt = list(range(1, 41))
    for n in (3, block, block + 1, 2 * block - 1, 2 * block, 40):
        keys = pc.keys_for(prompt[:n])
        assert [b for b, _ in keys] == list(range(block, n + 1, block))
        for b, key in keys:
            assert pc.keys_for(prompt[:b])[-1] == (b, key)
    n = 2 * block + 3
    eng.generate([GenerationRequest(prompt=prompt[:n], max_new_tokens=3)])
    got = sorted(e.tokens for e in pc._entries.values())
    assert got == list(range(block, n + 1, block))


def test_unshared_prompts_never_copy_and_a_repeat_hits_whole_blocks(
        params):
    """The benchmark's serve geometry in small (chunk 8, blocks of
    16): unshared prompts of lengths that are no block multiple run
    with NO copy-on-write (until PR 39 each left a mid-block entry its
    producer then copied); a repeated one still hits, a block multiple
    of tokens, and serves its cold stream."""
    rng = np.random.RandomState(39)
    reqs = [GenerationRequest(
        prompt=list(rng.randint(0, CFG.vocab_size, n)), max_new_tokens=6,
        request_id=i) for i, n in enumerate((17, 21, 25, 30, 19, 23))]
    want = _streams(_engine(params, block_size=16, prefix_cache=False),
                    [GenerationRequest(**r.__dict__) for r in reqs])
    eng = _engine(params, block_size=16)
    c0 = stat_get("STAT_generation_prefix_cow_copies")
    h0 = stat_get("STAT_generation_prefix_hits")
    t0 = stat_get("STAT_generation_prefix_hit_tokens")
    assert _streams(eng, reqs) == want
    assert stat_get("STAT_generation_prefix_hits") == h0
    assert all(e.tokens % 16 == 0 for e in eng.prefix_cache._entries.values())
    again = GenerationRequest(**reqs[1].__dict__)
    assert _streams(eng, [again]) == {1: want[1]}
    assert stat_get("STAT_generation_prefix_hits") == h0 + 1
    assert stat_get("STAT_generation_prefix_hit_tokens") - t0 == 16
    assert stat_get("STAT_generation_prefix_cow_copies") == c0


def test_lru_eviction_and_preemption_replay_under_kv_alloc_fault(
        params):
    """Pool pressure on a tiny pool forces the full ladder — LRU
    prefix eviction first, youngest preemption second — and the
    preempted sequences replay their re-admission through armed
    generation.kv_alloc faults (transient faults on a REPLAYED
    request retry instead of killing it); every stream still matches
    an uncontended cache-off run."""
    reqs = _shared_reqs(4)               # one per lane: all four are
    want = _streams(_engine(params, prefix_cache=False),  # first-
                    [GenerationRequest(**r.__dict__) for r in reqs])
    eng = _engine(params, num_blocks=14)  # admitted before arming
    pe0 = stat_get("STAT_generation_prefix_evictions")
    ev0 = stat_get("STAT_generation_evictions")
    for r in reqs:
        eng.submit(r)
    out = {}
    # run unarmed until pool pressure has preempted someone AND every
    # still-pending request is a replay (a first admission would be
    # KILLED by the fault — per-request isolation — not retried)
    while not eng.idle and (
            stat_get("STAT_generation_evictions") == ev0
            or any(s.evictions == 0 for s in eng._pending)):
        for r in eng.step():
            out[r.request_id] = r.tokens
    assert stat_get("STAT_generation_evictions") > ev0
    # manufacture one more replay so an ARMED re-admission is
    # guaranteed, then fault it once: the replayed request must retry
    # (not die) and drain to the exact cache-off streams
    assert eng._preempt_youngest()
    r0 = stat_get("STAT_generation_replay_retries")
    failpoints.arm_spec("generation.kv_alloc=raise@once")
    try:
        while not eng.idle:
            for r in eng.step():
                out[r.request_id] = r.tokens
    finally:
        failpoints.disarm("generation.kv_alloc")
    assert out == want
    assert stat_get("STAT_generation_replay_retries") == r0 + 1
    assert stat_get("STAT_generation_prefix_evictions") > pe0
    assert not eng.kv._tables            # everyone retired cleanly
    assert eng.kv.used_blocks == eng.prefix_cache.held_blocks


def test_prefix_lookup_fault_falls_back_cold_without_poisoning(
        params):
    """generation.prefix_lookup armed: admission must degrade to a
    cold prefill (identical stream, no token duplicated) and the
    cache must stay usable — the NEXT batch, fault disarmed, hits."""
    want = _streams(_engine(params, prefix_cache=False), _shared_reqs())
    eng = _engine(params)
    h0 = stat_get("STAT_generation_prefix_hits")
    with failpoints.armed("generation.prefix_lookup=raise"):
        assert _streams(eng, _shared_reqs()) == want
    assert stat_get("STAT_generation_prefix_hits") == h0  # all cold
    # publication still happened on the faulted batch: now it hits
    assert _streams(eng, _shared_reqs()) == want
    assert stat_get("STAT_generation_prefix_hits") > h0


def test_prefix_gauges_return_to_persisted_baseline(params):
    """Refcount-leak pin: after any number of batches the only live
    references are the cache's own — GAUGE_kv_shared_blocks and the
    occupancy gauges return to the persisted-prefix baseline, and
    clear() releases every block."""
    eng = _engine(params)
    _streams(eng, _shared_reqs())
    base = (gauge_get("GAUGE_kv_shared_blocks"),
            gauge_get("GAUGE_generation_blocks_used"),
            gauge_get("GAUGE_generation_prefix_blocks"))
    assert base[1] == eng.prefix_cache.held_blocks
    _streams(eng, _shared_reqs())        # warm pass: pure reuse
    assert (gauge_get("GAUGE_kv_shared_blocks"),
            gauge_get("GAUGE_generation_blocks_used"),
            gauge_get("GAUGE_generation_prefix_blocks")) == base
    eng.prefix_cache.clear()
    assert gauge_get("GAUGE_kv_shared_blocks") == 0
    assert gauge_get("GAUGE_kv_blocks_saved") == 0
    assert gauge_get("GAUGE_generation_blocks_used") == 0
    assert gauge_get("GAUGE_generation_prefix_entries") == 0
    assert gauge_get("GAUGE_generation_prefix_blocks") == 0


# ---------------------------------------------------------------------------
# speculative decoding: bitwise parity with plain decode (tentpole b)
# ---------------------------------------------------------------------------

# repetitive prompts give the ngram drafter real matches
def _spec_reqs():
    base = [5, 9, 2, 5, 9, 2, 5, 9, 2, 5, 9]
    out = []
    for i, sp in enumerate([
            SamplingParams(),
            SamplingParams(temperature=0.8, seed=11),
            SamplingParams(temperature=0.9, top_k=8, seed=22),
            SamplingParams(temperature=0.7, top_p=0.9, seed=33)]):
        out.append(GenerationRequest(
            prompt=base + [i], max_new_tokens=10, sampling=sp,
            request_id=i))
    return out


def test_spec_streams_bitwise_identical_across_samplers(params):
    """THE speculation contract: greedy, temperature, top-k and top-p
    lanes all emit bitwise the plain-decode stream while the drafter
    proposes (fold_in(seed, position) keys make verify rows exact)."""
    want = _streams(_engine(params), _spec_reqs())
    p0 = stat_get("STAT_generation_spec_proposed")
    eng = _engine(params, spec_tokens=3)
    assert _streams(eng, _spec_reqs()) == want
    assert stat_get("STAT_generation_spec_proposed") > p0


def test_spec_model_drafter_accepts_and_matches(params):
    """draft='model' with the TARGET's own weights: greedy proposals
    equal greedy choices, so acceptance is total — and the stream is
    still bitwise plain decode."""
    req = GenerationRequest(prompt=[3, 1, 4, 1, 5], max_new_tokens=12,
                            request_id="g")
    want = _streams(_engine(params), [req])
    p0 = stat_get("STAT_generation_spec_proposed")
    a0 = stat_get("STAT_generation_spec_accepted")
    eng = _engine(params, spec_tokens=2, draft="model",
                  draft_cfg=CFG, draft_params=params)
    assert _streams(eng, [GenerationRequest(**req.__dict__)]) == want
    prop = stat_get("STAT_generation_spec_proposed") - p0
    acc = stat_get("STAT_generation_spec_accepted") - a0
    assert prop > 0 and acc == prop


def test_draft_fault_falls_back_to_plain_decode(params):
    """generation.draft_step armed: the step degrades to plain decode
    — bitwise-identical stream, zero proposals, fault counted."""
    want = _streams(_engine(params), _spec_reqs())
    eng = _engine(params, spec_tokens=3)
    p0 = stat_get("STAT_generation_spec_proposed")
    f0 = stat_get("STAT_generation_draft_faults")
    with failpoints.armed("generation.draft_step=raise"):
        assert _streams(eng, _spec_reqs()) == want
    assert stat_get("STAT_generation_spec_proposed") == p0
    assert stat_get("STAT_generation_draft_faults") > f0


def test_spec_with_prefix_cache_composes(params):
    """Both tentpole halves at once: cached admission feeding
    speculative decode still reproduces the cold plain-decode streams
    and leaves no dangling references."""
    want = _streams(_engine(params, prefix_cache=False), _shared_reqs())
    eng = _engine(params, spec_tokens=2)
    assert _streams(eng, _shared_reqs()) == want
    assert _streams(eng, _shared_reqs()) == want  # warm + drafting
    assert not eng.kv._tables
    assert eng.kv.used_blocks == eng.prefix_cache.held_blocks


def test_spec_requires_chunked_mode_and_validates_draft(params):
    # the two-phase engine is gone: no chunk, no engine for a drafter
    with pytest.raises(ValueError, match="prefill_chunk must be >= 1"):
        GenerationEngine(CFG, params, num_blocks=16, block_size=4,
                         decode_width=2, prefill_chunk=0, spec_tokens=2)
    with pytest.raises(ValueError):
        _engine(params, spec_tokens=2, draft="model")  # no draft_cfg
    with pytest.raises(ValueError):
        _engine(params, spec_tokens=2, draft="banana")


# ---------------------------------------------------------------------------
# PR 37: the ledger's gauges are running counts — a recount oracle, a
# count of what one mutation costs as the pool grows, an engine run
# ---------------------------------------------------------------------------

def _recount(kv, cache):
    """The formulas the ledger ran on every mutation until PR 37: a
    pass over every reference count and every entry's block list."""
    refs = list(kv._ref.values())
    held = set()
    for e in cache._entries.values():
        held.update(e.blocks)
    return (sum(1 for r in refs if r > 1),
            sum(r - 1 for r in refs if r > 1), len(held))


def _assert_counts_exact(kv, cache, what):
    """The three properties and the six gauges equal a recount."""
    want = _recount(kv, cache)
    assert (kv.shared_blocks, kv.blocks_saved,
            cache.held_blocks) == want, what
    assert (gauge_get("GAUGE_kv_shared_blocks"),
            gauge_get("GAUGE_kv_blocks_saved"),
            gauge_get("GAUGE_generation_prefix_blocks")) == want, what
    assert gauge_get("GAUGE_generation_blocks_free") == len(kv._free), what
    assert gauge_get("GAUGE_generation_blocks_used") == kv.used_blocks, what
    assert gauge_get("GAUGE_generation_prefix_entries") == len(
        cache._entries), what
    assert all(r >= 1 for r in kv._ref.values()), what
    assert len(kv._ref) == kv.used_blocks, what


@pytest.mark.parametrize("seed", range(8))
def test_ledger_counts_equal_a_recount_after_every_call(seed):
    """A few thousand random ledger and cache calls over a pool small
    enough to run dry, the allocation failpoint raised on the way:
    after EVERY call the three properties and the six gauges equal a
    recount from `_ref` and the entries."""
    rng = np.random.RandomState(1000 + seed)
    kv = KVCacheManager(num_blocks=int(rng.choice([12, 24, 40])),
                        block_size=4)
    cache = PrefixCache(kv)
    # few distinct prompts over two stems, so chains are shared,
    # matched and re-inserted
    stems = [list(rng.randint(0, 64, 16)) for _ in range(2)]
    prompts = [stems[i % 2][:int(rng.choice([8, 16]))]
               + list(rng.randint(0, 64, int(rng.randint(0, 14))))
               for i in range(6)]
    live, extra, ids = {}, [], iter(range(10 ** 6))
    raised = {"exhausted": 0, "fault": 0, "private": 0}

    def pick():
        return list(live)[rng.randint(len(live))] if live else None

    def attach():
        prompt = prompts[rng.randint(len(prompts))]
        hit = cache.match(prompt) if rng.rand() < 0.7 else None
        shared = hit[1] if hit else []
        if not shared and live and rng.rand() < 0.3:
            shared = kv.owned(pick())[:rng.randint(1, 3)]
        need = max(0, kv.blocks_for_tokens(len(prompt)) - len(shared))
        sid = next(ids)
        spec = ("generation.kv_alloc=raise@once"
                if rng.rand() < 0.1 else "")
        try:
            with failpoints.armed(spec):
                kv.attach(sid, shared, need)
        except BlockPoolExhausted:
            raised["exhausted"] += 1
        except InjectedFault:
            raised["fault"] += 1
        else:
            live[sid] = prompt

    def extend():
        try:
            kv.extend(pick())
        except BlockPoolExhausted:
            raised["exhausted"] += 1

    def cow():
        sid = pick()
        try:
            kv.cow(sid, rng.randint(len(kv.owned(sid))))
        except BlockPoolExhausted:
            raised["exhausted"] += 1
        except ValueError:
            raised["private"] += 1

    def incref():
        blocks = kv.owned(pick())[:rng.randint(1, 4)]
        kv.incref(blocks)
        extra.append(blocks)

    def decref():
        if extra:
            kv.decref(extra.pop(rng.randint(len(extra))))

    def free():
        # now and then an id that is gone: a no-op, not an underflow
        sid = pick() if rng.rand() < 0.9 else -1
        (kv.evict if rng.rand() < 0.3 else kv.free)(sid)
        live.pop(sid, None)

    def insert():
        sid = pick()
        owned = kv.owned(sid)
        for tokens, key in cache.keys_for(live[sid]):
            n = kv.blocks_for_tokens(tokens)
            if n > len(owned) or rng.rand() < 0.2:
                break
            cache.insert(key, tokens, owned[:n])
            _assert_counts_exact(kv, cache, "insert")

    def match():
        cache.match(prompts[rng.randint(len(prompts))])

    def evict_for():
        cache.evict_for(kv.free_blocks + int(rng.randint(0, 4)))

    ops = [(attach, 5, False), (extend, 3, True), (cow, 3, True),
           (incref, 1, True), (decref, 1, False), (free, 4, False),
           (insert, 5, True), (match, 1, False), (evict_for, 2, False),
           (cache.clear, 0.1, False)]
    weights = np.array([w for _, w, _ in ops], float)
    _assert_counts_exact(kv, cache, "fresh")
    for _ in range(2500):
        op, _, needs_live = ops[rng.choice(len(ops), p=weights
                                           / weights.sum())]
        if needs_live and not live:
            continue
        op()
        _assert_counts_exact(kv, cache, op.__name__)
    # the walk reached what it claims to cover
    assert raised["exhausted"] and raised["fault"] and raised["private"]
    for sid in list(live):
        kv.free(sid)
    for blocks in extra:
        kv.decref(blocks)
    cache.clear()
    _assert_counts_exact(kv, cache, "drained")
    assert kv.used_blocks == 0 and cache.held_blocks == 0


def _full_cache(num_blocks):
    """A ledger whose prefix cache holds the whole pool but three
    blocks: retired requests of 4 blocks of 16 tokens, an entry at
    every block boundary, and one live sequence whose first two
    blocks a second table shares."""
    kv = KVCacheManager(num_blocks=num_blocks, block_size=16)
    cache = PrefixCache(kv)
    live = kv.alloc("live", 4)
    kv.attach("twin", live[:2], 0)
    sid = 0
    while kv.free_blocks >= 4:
        owned = kv.alloc(sid, 4)
        for i in range(1, 5):
            cache.insert("%d/%d" % (sid, i), 16 * i, owned[:i])
        kv.free(sid)
        sid += 1
    assert kv.free_blocks == 3 and cache.held_blocks == 4 * sid
    assert cache.held_blocks >= num_blocks - 12
    return kv, cache, live


_MUTATIONS = {
    "extend": lambda kv, cache, live: kv.extend("live"),
    "cow": lambda kv, cache, live: kv.cow("twin", 1),
    "insert": lambda kv, cache, live: cache.insert(
        "live/4", 32, live[:2]),
    "evict_for": lambda kv, cache, live: cache.evict_for(
        kv.free_blocks + 1),
    "free": lambda kv, cache, live: kv.free("live"),
}


def _calls_of(mutation, num_blocks):
    """Python calls, generator resumptions and C calls
    (`sys.setprofile`) one mutation makes on a full cache."""
    kv, cache, live = _full_cache(num_blocks)
    calls = [0]

    def count(frame, event, arg):
        if event in ("call", "c_call"):
            calls[0] += 1

    sys.setprofile(count)
    try:
        _MUTATIONS[mutation](kv, cache, live)
    finally:
        sys.setprofile(None)
    _assert_counts_exact(kv, cache, mutation)
    return calls[0]


@pytest.mark.parametrize("mutation", sorted(_MUTATIONS))
def test_a_ledger_mutation_costs_what_it_touches_not_the_pool(mutation):
    """COUNTED, not timed: one mutation of a full cache makes the same
    number of calls at 64 blocks and at 4,096. With the gauges
    recounted on every mutation the count grew with the pool (a
    generator resumed per reference count, a `set.update` per
    entry)."""
    small, large = (_calls_of(mutation, n) for n in (64, 4096))
    assert small == large, (mutation, small, large)


def test_engine_gauges_equal_a_recount_through_hits_cow_and_preemption(
        params):
    """The engine's own traffic over a pool too small for it: prefix
    hits, copy-on-write (every other prompt an exact duplicate of
    8 tokens on blocks of 4, whose re-run last token lands in a shared
    block), LRU eviction and preemption. After every step and at the
    end the gauges equal the recount."""
    reqs = [GenerationRequest(
        prompt=PREFIX[:8] + ([30 + i, 31 + i, 32 + i] * 2 if i % 2
                             else []),
        max_new_tokens=8, sampling=SamplingParams(), request_id=i)
        for i in range(10)]
    eng = _engine(params, prefill_chunk=6, num_blocks=18)
    before = {k: stat_get(k) for k in (
        "STAT_generation_prefix_hits", "STAT_generation_prefix_cow_copies",
        "STAT_generation_prefix_evictions", "STAT_generation_evictions")}
    for r in reqs:
        eng.submit(r)
    done = 0
    while not eng.idle:
        done += len(eng.step())
        _assert_counts_exact(eng.kv, eng.prefix_cache, "step")
    assert done == len(reqs)
    for k, v in before.items():
        assert stat_get(k) > v, k
    assert not eng.kv._tables
    assert eng.kv.used_blocks == eng.prefix_cache.held_blocks
    _assert_counts_exact(eng.kv, eng.prefix_cache, "drained")
    eng.prefix_cache.clear()
    _assert_counts_exact(eng.kv, eng.prefix_cache, "cleared")
    assert eng.kv.used_blocks == 0
