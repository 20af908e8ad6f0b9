"""The mixed step with LOOKAHEAD (docs/generation.md "One step ahead
of the host"; what the engine does wherever the step allows it): step n
is planned and dispatched before step n-1's tokens are fetched; the
tokens feed the next step on the device. `lookahead=0` is the engine
without it, kept for speculation and held here as the reference.

Held here, on XLA:CPU at tiny widths, for both model families: the
streams are those of the engine without lookahead and of the O(N^2)
oracle (greedy and sampled, EOS seen one step late, preemption with a
step out, prefix-cache hits, int8 KV), a step is on the device while
the host fetches the one before, a sequence at its length takes no
further slot, what cannot ride it is refused, and a pool whose engine
faults forgets the step that was out.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                   GenerationPool, GenerationRequest,
                                   NaiveGenerator, SamplingParams,
                                   init_params)
from paddle_tpu.generation import looped
from paddle_tpu.generation.looped import LoopedDecoderConfig
from paddle_tpu.monitor import stat_get, timer_get


def _family(name):
    if name == "gpt":
        cfg = DecoderConfig(vocab_size=96, hidden=32, layers=2, heads=4,
                            max_seq_len=64)
        return cfg, init_params(cfg, seed=3)
    cfg = LoopedDecoderConfig(
        vocab_size=96, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, head_dim=12,
        intermediate_size=80, total_ut_steps=2, max_seq_len=64)
    return cfg, looped.init_params(cfg, seed=3)


FAMILY = pytest.mark.parametrize("family", ["gpt", "looped"])


def _engine(cfg, params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("decode_width", 4)
    kw.setdefault("prefill_chunk", 8)
    return GenerationEngine(cfg, params, **kw)


def _reqs(n=7, new=9, eos=None):
    sps = [SamplingParams(), SamplingParams(temperature=0.8, seed=101),
           SamplingParams(temperature=0.9, top_k=8, seed=202)]
    return [GenerationRequest(prompt=list(range(3 + i, 12 + 3 * i)),
                              max_new_tokens=new + (i % 3), request_id=i,
                              sampling=sps[i % len(sps)], eos_token=eos)
            for i in range(n)]


def _streams(results):
    return {r.request_id: (list(r.tokens), r.finish_reason)
            for r in results}


@FAMILY
def test_streams_equal_the_engines_without_lookahead_and_the_oracles(
        family):
    cfg, params = _family(family)
    ahead = _streams(_engine(cfg, params, lookahead=1).generate(_reqs()))
    plain = _streams(_engine(cfg, params, lookahead=0).generate(_reqs()))
    assert ahead == plain
    naive = NaiveGenerator(cfg, params)
    for r in _reqs():
        assert ahead[r.request_id][0] == list(naive.generate(r).tokens)


@FAMILY
def test_an_eos_is_seen_one_step_late_and_the_stream_is_the_same(family):
    cfg, params = _family(family)
    free = _streams(_engine(cfg, params, lookahead=0).generate(_reqs()))
    # an EOS that every greedy stream meets somewhere inside
    eos = free[0][0][3]
    plain = _streams(_engine(cfg, params, lookahead=0)
                     .generate(_reqs(eos=eos)))
    ahead = _streams(_engine(cfg, params, lookahead=1)
                     .generate(_reqs(eos=eos)))
    assert ahead == plain
    assert any(reason == "eos" for _, reason in ahead.values())
    assert any(reason == "length" for _, reason in ahead.values())


@FAMILY
def test_preemption_with_a_step_out_replays_to_the_same_streams(family):
    cfg, params = _family(family)
    reqs = _reqs(n=6, new=14)
    want = _streams(_engine(cfg, params, lookahead=0).generate(reqs))
    # 4 lanes of up to 10 blocks each do not fit 20: the youngest is
    # evicted and replayed, with lookahead after the step out is in
    eng = _engine(cfg, params, lookahead=1, num_blocks=20,
                  prefix_cache=False)
    got = eng.generate(reqs)
    assert _streams(got) == want
    assert any(r.evictions for r in got)


@FAMILY
def test_prefix_hits_serve_the_same_streams(family):
    cfg, params = _family(family)
    shared = list(range(5, 29))
    reqs = [GenerationRequest(prompt=shared + [40 + i], max_new_tokens=6,
                              request_id=i) for i in range(6)]
    want = _streams(_engine(cfg, params, prefix_cache=False, lookahead=0)
                    .generate(reqs))
    hits = stat_get("STAT_generation_prefix_hits")
    got = _streams(_engine(cfg, params, lookahead=1).generate(reqs))
    assert got == want
    assert stat_get("STAT_generation_prefix_hits") > hits


def test_int8_kv_rides_the_lookahead_too():
    cfg, params = _family("gpt")
    want = _streams(_engine(cfg, params, kv_dtype="int8", lookahead=0)
                    .generate(_reqs()))
    got = _streams(_engine(cfg, params, kv_dtype="int8", lookahead=1)
                   .generate(_reqs()))
    assert got == want


@FAMILY
@pytest.mark.parametrize("tight", [False, True])
def test_a_step_is_out_while_the_host_fetches_the_one_before(family,
                                                              tight):
    """The order of one call: plan, dispatch, THEN fetch the step
    before; the dispatched step feeds on device tokens. `tight`: the
    pool is full of cached prefixes, and evicting one needs no wait for
    the device (on the chip a collect inside the plan made a bubble of
    every fourth step: PERF.md, PR 29)."""
    cfg, params = _family(family)
    eng = _engine(cfg, params, lookahead=1,
                  num_blocks=36 if tight else 64)
    evicted = stat_get("STAT_generation_prefix_evictions")
    preempted = stat_get("STAT_generation_evictions")
    log = []
    run, collect = eng._run, eng._collect

    def spy_run(kind, *rest):
        if kind == "mixed":
            ints = np.asarray(rest[1])
            t, m = eng.token_budget, eng.max_blocks_per_seq
            feed_rows = ints[t * m + 2 * t: t * m + 3 * t]
            log.append(("dispatch", int((feed_rows >= 0).sum()),
                        rest[0] is eng._no_prev))
        return run(kind, *rest)

    planning = []
    plan = eng._plan_mixed

    def spy_plan(finished):
        planning.append(1)
        try:
            return plan(finished)
        finally:
            planning.pop()

    def spy_collect():
        log.append(("collect", bool(planning)))
        return collect()
    eng._run, eng._collect, eng._plan_mixed = spy_run, spy_collect, spy_plan
    eng.generate(_reqs(n=10 if tight else 4, new=6))
    if tight:
        assert stat_get("STAT_generation_prefix_evictions") > evicted
    assert stat_get("STAT_generation_evictions") == preempted
    # no plan had to wait for the device
    assert not any(e[1] for e in log if e[0] == "collect")
    kinds = [e[0] for e in log]
    # every collect but the last comes straight after a dispatch: the
    # device holds the next step before the host asks for this one
    assert kinds[0] == "dispatch" and kinds[-1] == "collect"
    for i, k in enumerate(kinds[:-1]):
        if k == "collect":
            assert kinds[i - 1] == "dispatch"
    assert kinds.count("collect") == kinds.count("dispatch")
    # once lanes decode, their tokens come from the device
    fed = [e for e in log if e[0] == "dispatch" and e[1] > 0]
    assert fed and all(not e[2] for e in fed)
    assert log[0][2]                    # the first step has no step before
    assert eng._inflight is None and eng.idle


def test_a_sequence_at_its_length_takes_no_further_slot():
    cfg, params = _family("gpt")
    eng = _engine(cfg, params, lookahead=1, decode_width=2)
    pads = stat_get("STAT_generation_pad_tokens")
    toks = stat_get("STAT_generation_tokens")
    steps = timer_get("TIMER_generation_mixed_step_us")["count"]
    out = eng.generate([GenerationRequest(prompt=[5, 6, 7],
                                          max_new_tokens=4,
                                          request_id=0)])
    assert len(out[0].tokens) == 4
    assert stat_get("STAT_generation_tokens") - toks == 4
    # one prefill step (3 prompt tokens) and three decode steps: the
    # fourth token ends the request, and no fifth slot is spent on it
    n_steps = timer_get("TIMER_generation_mixed_step_us")["count"] - steps
    assert n_steps == 4
    assert stat_get("STAT_generation_pad_tokens") - pads == \
        4 * eng.token_budget - (3 + 3)


@pytest.mark.parametrize("kw", [dict(spec_tokens=2),
                                dict(lookahead=2)])
def test_what_cannot_ride_the_lookahead_is_refused_or_runs_without(kw):
    cfg, params = _family("gpt")
    if "lookahead" not in kw:
        # left to itself the engine sees that the step cannot
        assert _engine(cfg, params, **kw).lookahead == 0
    kw.setdefault("lookahead", 1)
    with pytest.raises(ValueError, match="lookahead"):
        _engine(cfg, params, **kw)


@FAMILY
def test_the_pool_serves_through_it_and_forgets_a_faulted_step(family):
    cfg, params = _family(family)
    want = _streams(_engine(cfg, params, lookahead=0).generate(_reqs()))
    eng = _engine(cfg, params)
    assert eng.lookahead == 1           # what the engine does by itself
    pool = GenerationPool(eng)
    try:
        futs = [pool.submit(r) for r in _reqs()]
        got = {r.request_id: (list(f.result(timeout=120).tokens),
                              f.result().finish_reason)
               for r, f in zip(_reqs(), futs)}
        assert got == want
        # a step left out by a fault is dropped with the rest
        eng._inflight = (jnp.zeros((eng.sample_width,), jnp.int32),
                         [], [], 0.0)
        pool._reset_engine()
        assert eng._inflight is None
        f = pool.submit(_reqs()[0])
        assert list(f.result(timeout=120).tokens) == want[0][0]
    finally:
        pool.close()
