"""The KV pools are device state updated IN PLACE (docs/generation.md,
"The pools are donated state"): every compiled program that writes
them donates them, and writes its rows into the arrays it was given.

Pinned here, on XLA:CPU with the tiny decoder: what the compiled text
of the `mixed`, `cow`, `draft_mixed` and `draft_cow` programs may hold
(every pool parameter aliased to an output; no copy, concatenate or
whole-pool update chain with a pool-shaped result), what a step and a
warm-up leave of the arrays the engine held, that the streams are
still the oracle's, and that a fault raised from inside a donated call
is followed by live zero pools and a correct answer.
"""
import re
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                   GenerationPool, GenerationRequest,
                                   NaiveGenerator, SamplingParams,
                                   init_params)
from paddle_tpu.monitor import stat_get
from paddle_tpu.serving import PoolRestarted, ServingQueueFull

CFG = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=4,
                    max_seq_len=48)
POOLS = ("k_pools", "v_pools", "k_scales", "v_scales", "dk_pools",
         "dv_pools")


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, seed=0)


@pytest.fixture
def flag_guard():
    from paddle_tpu import flags as F
    saved = dict(F._values)
    yield
    F._values.clear()
    F._values.update(saved)


def _engine(params, **kw):
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("decode_width", 4)
    kw.setdefault("prefill_chunk", 8)
    return GenerationEngine(CFG, params, **kw)


def _held(eng):
    """name -> the pool array the engine holds now."""
    return {n: getattr(eng, n) for n in POOLS
            if getattr(eng, n, None) is not None}


def _reqs():
    """Greedy and sampled lanes over one shared 16-token prefix, so the
    prefix cache publishes whole blocks, and last the prefix alone: an
    exact duplicate of four cached blocks, admitted once a lane frees,
    whose re-run last token lands in a shared block, so `cow` (and
    `draft_cow`) run on the live pools."""
    prefix = [7, 3, 11, 2, 9, 14, 5, 8, 21, 4, 13, 6, 17, 10, 1, 12]
    sps = [SamplingParams(),
           SamplingParams(temperature=0.8, seed=101),
           SamplingParams(temperature=0.9, top_k=8, seed=202),
           SamplingParams(temperature=0.7, top_p=0.9, seed=303)]
    return [GenerationRequest(prompt=prefix + [40 + i, 41 + i],
                              max_new_tokens=6, sampling=sp, request_id=i)
            for i, sp in enumerate(sps)] + [GenerationRequest(
                prompt=list(prefix), max_new_tokens=6,
                sampling=SamplingParams(temperature=0.8, seed=404),
                request_id=len(sps))]


_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\w+\[[\d,]*\])\S* ([\w\-]+)\(")
# the only operations that may RESULT in a pool, or in a layer of one:
# the program's arguments and the in-place updates (alone or as the
# root of a fusion)
_IN_PLACE = {"scatter", "dynamic-update-slice"}
_ALLOWED = _IN_PLACE | {"parameter", "fusion"}


def _pool_shaped(pool):
    """HLO type strings of `pool` and of one layer of it."""
    dt = {"float32": "f32", "int8": "s8"}[str(pool.dtype)]
    dims = [str(d) for d in pool.shape]
    return {"%s[%s]" % (dt, ",".join(d)) for d in
            (dims, ["1"] + dims[1:], dims[1:])}


def _check_program(txt, pools, argnums, weights=None):
    """`txt`: a compiled program's text; `pools`: the arrays it was
    given at the positions `argnums`, after the tree `weights` where
    the first position is 1 (the compiled module numbers the leaves)."""
    head = txt.splitlines()[0]
    assert "input_output_alias" in head, head[:200]
    alias = head[head.index("input_output_alias"):]
    alias = alias[:alias.index("}, entry_computation_layout")]
    aliased = {int(p) for p in re.findall(r"\((\d+), \{\}", alias)}
    if argnums[0] == 1:
        leaves = len(jax.tree.leaves(weights))
        argnums = tuple(leaves + i - 1 for i in argnums)
    assert aliased == set(argnums), (aliased, argnums)
    shapes = set().union(*(_pool_shaped(p) for p in pools))
    seen = {}
    for line in txt.splitlines():
        m = _INSTR.match(line)
        if m and m.group(1) in shapes:
            seen[m.group(2)] = seen.get(m.group(2), 0) + 1
    assert set(seen) <= _ALLOWED, seen
    updates = sum(seen.get(op, 0) for op in _IN_PLACE)
    assert updates > 0, seen
    # a pool-shaped fusion is an in-place update's wrapper, no more
    assert seen.get("fusion", 0) <= updates, seen


@pytest.mark.parametrize("kv", ["fp32", "int8"])
@pytest.mark.parametrize("cached", [False, True],
                         ids=["plain_jit", "program_cache"])
def test_pool_programs_update_in_place(tmp_path, params, cached, kv):
    eng = _engine(params, spec_tokens=2, draft="model", draft_cfg=CFG,
                  draft_params=params, prefix_cache=True, kv_dtype=kv,
                  program_cache_dir=str(tmp_path / "pc") if cached
                  else None)
    made = _held(eng)
    assert set(made) == set(eng._pool_specs())
    eng.warmup()
    # warm-up ran every program on the pools and kept what came back
    for name, (shape, dtype, _) in eng._pool_specs().items():
        pool = getattr(eng, name)
        assert not pool.is_deleted(), name
        assert pool.shape == shape and pool.dtype == dtype, name
        assert made[name].is_deleted(), name
    for kind in ("mixed", "cow", "draft_mixed", "draft_cow"):
        argnums = eng._pool_argnums(kind)
        names = eng._program_pools(kind)
        assert len(names) == len(argnums) > 0, kind
        _check_program(eng._fns[kind]._compiled.as_text(),
                       [getattr(eng, n) for n in names], argnums,
                       eng.draft_params if kind == "draft_mixed"
                       else eng.params)
    # a step leaves every array the engine held before it dead
    reqs = _reqs()
    for r in reqs[:-1]:
        eng.submit(r)
    before = _held(eng)
    out = {r.request_id: r.tokens for r in eng.step()}
    for name, old in before.items():
        if name.startswith("d"):
            continue            # the drafter runs only beside a decode
        assert old.is_deleted(), name
        assert not getattr(eng, name).is_deleted(), name
    # the duplicate, after the first step has published: it hits and
    # copies a shared block inside the steps checked below
    eng.submit(reqs[-1])
    cow0 = stat_get("STAT_generation_compile")
    copies0 = stat_get("STAT_generation_prefix_cow_copies")
    while not eng.idle:
        before = _held(eng)
        for r in eng.step():
            out[r.request_id] = r.tokens
    assert all(a.is_deleted() for a in before.values())
    assert stat_get("STAT_generation_compile") == cow0
    assert stat_get("STAT_generation_prefix_cow_copies") > copies0
    # the streams are still the oracle's (int8 KV: the plain int8
    # engine's, which speculation reproduces bitwise)
    if kv == "fp32":
        naive = NaiveGenerator(CFG, params, attn_lanes=eng.attn_lanes)
        want = {r.request_id: naive.generate(r).tokens for r in reqs}
    else:
        want = {r.request_id: r.tokens for r in _engine(
            params, prefix_cache=False, kv_dtype=kv).generate(_reqs())}
    assert out == want


@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_pallas_form_reads_the_stacked_flat_pools(params, kv):
    """The blocked kernel takes a `[1, bs, H * D]` tile of the pools
    the engine updates in place (interpret mode here) and serves the
    reference form's streams."""
    want = {r.request_id: r.tokens for r in _engine(
        params, kv_dtype=kv).generate(_reqs())}
    eng = _engine(params, kernel="pallas", kv_dtype=kv)
    before = _held(eng)
    got = {r.request_id: r.tokens for r in eng.generate(_reqs())}
    assert got == want
    # (interpret mode threads whole operands through its loops, so
    # the compiled text says nothing of the chip here:
    # tests/test_chip_compile.py compiles the same tile for a v5e)
    assert all(a.is_deleted() for a in before.values())


@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_fault_inside_a_donated_call_leaves_live_zero_pools(flag_guard,
                                                            params, kv):
    """The compiled step deletes its pool arguments and raises, as a
    fault while a donated call runs does: the pool's supervisor fails
    the in-flight request, `_reset_engine` makes the dead pools anew
    (zeros; scale pools ones), and the next request is served
    correctly."""
    pt.set_flags({"FLAGS_pool_restart_backoff_ms": 1.0,
                  "FLAGS_pool_max_restarts": 3})
    eng = _engine(params, kv_dtype=kv)
    eng.warmup()
    specs = eng._pool_specs()
    key = "mixed"
    real = eng._fns[key]
    argnums = eng._pool_argnums("mixed")
    state = {"armed": False, "seen": None}

    def faulty(*args):
        if not state["armed"]:
            return real(*args)
        state["armed"] = False
        for i in argnums:
            args[i].delete()
        state["seen"] = {n: getattr(eng, n).is_deleted() for n in specs}
        raise RuntimeError("device fault inside the donated step")
    eng._fns[key] = faulty

    def req():
        return GenerationRequest(prompt=[3, 1, 4, 1, 5], max_new_tokens=5,
                                 sampling=SamplingParams(temperature=0.8,
                                                         seed=9))
    pool = GenerationPool(eng)
    try:
        base = pool.run(req(), timeout=120.0)
        r0 = stat_get("STAT_generation_restarts")
        state["armed"] = True
        with pytest.raises(PoolRestarted):
            pool.run(req(), timeout=120.0)
        # the engine really was left holding dead arrays
        assert state["seen"] == {n: True for n in specs}
        out, deadline = None, time.monotonic() + 30.0
        while time.monotonic() < deadline:
            try:
                out = pool.run(req(), timeout=10.0)
                break
            except (PoolRestarted, ServingQueueFull, TimeoutError):
                time.sleep(0.05)
        assert out is not None and out.tokens == base.tokens
        assert stat_get("STAT_generation_restarts") == r0 + 1
    finally:
        pool.close()
    # what _reset_engine rebuilt, seen on an engine nothing has
    # stepped since: live pools of the right shape and dtype, zeros
    # (scale pools ones)
    for n in specs:
        getattr(eng, n).delete()
    pool2 = GenerationPool(eng, _start=False)
    try:
        pool2._reset_engine()
    finally:
        pool2.close()
    for n, (shape, dtype, fill) in specs.items():
        got = getattr(eng, n)
        assert not got.is_deleted() and got.shape == shape \
            and got.dtype == dtype, n
        assert np.array_equal(np.asarray(got),
                              np.full(shape, fill, dtype)), n
    assert eng._restore_pools() == []     # nothing left to make
