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
unpoisoned cache, draft_step -> plain decode)."""
import numpy as np
import pytest

from paddle_tpu import failpoints
from paddle_tpu.failpoints import InjectedFault
from paddle_tpu.generation import (BlockPoolExhausted, DecoderConfig,
                                   GenerationEngine, GenerationRequest,
                                   KVCacheManager, SamplingParams,
                                   TRASH_BLOCK, init_params)
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


# a 16-token prefix = two full chunks of 8; suffixes diverge after it
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
    """chunk 6 on block_size 4 puts the cached boundary MID-block:
    every consumer's first write lands in a still-shared block and
    must copy-on-write, while the producer keeps decoding — streams
    stay bitwise-identical to a no-sharing run."""
    shared6 = PREFIX[:6]
    reqs = [GenerationRequest(
        prompt=shared6 + [30 + i, 31 + i, 32 + i], max_new_tokens=5,
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
