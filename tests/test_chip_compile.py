"""Compile the main paths' Pallas kernels for a described TPU v5e.

No chip is attached here: the installed TPU compiler lowers for a
device that is only described (`v5e:2x2`), which catches what
interpret mode cannot — a block the Mosaic tiling refuses, too much
VMEM, an op with no TPU lowering. Shapes are the real widths of
chip_smoke.py: BERT-base attention and layer norm at B=32 S=512, and
the GPT-2-small paged pool of the generation engine.

The kernels' own `_use_interpret()` sees the CPU under pytest, so each
test calls the inner function with `interpret=False` itself.

All of these live in ONE file and describe the topology inside a
module-scoped fixture: only one process may load the TPU library, so
the call must not run at import or collection time, and a second file
could land on another xdist worker.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip("no v5e:2x2 topology can be described here: %r" % (e,))
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip; keep it off here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *avals):
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in avals]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    return txt


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# BERT-base attention at the train phase's batch: [B, H, S, D] bf16 with
# the [B, 1, 1, S] padding bias MultiHeadAttention passes
_QKV = _sds((32, 12, 512, 64), jnp.bfloat16)
_BIAS = _sds((32, 1, 1, 512), jnp.float32)
_SEED = _sds((1, 1), jnp.int32)


def _flash_call(with_seed):
    from paddle_tpu.kernels.flash_attention import _flash
    keep = 0.9 if with_seed else 1.0

    def fwd(q, k, v, bias, *seed):
        return _flash(q, k, v, bias, None, seed[0] if seed else None,
                      False, 1.0 / math.sqrt(64), 512, 512, False, keep,
                      False)
    return fwd


@pytest.mark.parametrize("with_seed", [True, False],
                         ids=["inkernel_dropout", "no_dropout"])
def test_flash_forward_compiles_for_v5e(one_chip, with_seed):
    avals = (_QKV, _QKV, _QKV, _BIAS) + ((_SEED,) if with_seed else ())
    _compile(_flash_call(with_seed), one_chip, *avals)


@pytest.mark.parametrize("seq", [512, 1024], ids=["s512", "s1024"])
@pytest.mark.parametrize("with_seed", [True, False],
                         ids=["inkernel_dropout", "no_dropout"])
def test_flash_backward_compiles_for_v5e(one_chip, with_seed, seq):
    """At S=1024 the blocks of 512 make two key blocks: the backward's
    dQ is summed across the grid's key axis in its VMEM scratch."""
    fwd = _flash_call(with_seed)

    def loss(q, k, v, *rest):
        return jnp.sum(fwd(q, k, v, *rest).astype(jnp.float32))
    qkv = _sds((32, 12, seq, 64), jnp.bfloat16)
    avals = (qkv, qkv, qkv, _sds((32, 1, 1, seq), jnp.float32)) \
        + ((_SEED,) if with_seed else ())
    txt = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, *avals)
    # the forward kernel and ONE backward kernel (dQ, dK and dV)
    assert txt.count("tpu_custom_call") == 2


def test_layer_norm_compiles_for_v5e(one_chip):
    from paddle_tpu.kernels.layer_norm import _layer_norm

    def loss(x, g, b):
        return jnp.sum(_layer_norm(x, g, b, 1e-5, False)
                       .astype(jnp.float32))
    x = _sds((16384, 768), jnp.bfloat16)
    gb = _sds((768,), jnp.float32)
    txt = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, x, gb, gb)
    assert txt.count("tpu_custom_call") >= 2  # forward + backward


# GPT-2-small heads over the generate phase's pool. q [8, 16, ...] is
# the ragged chunk form; [16, 1, ...] is what the engine's mixed step
# traces (one slot per token of its default 16-slot budget)
@pytest.mark.parametrize("q_shape", [(8, 16, 12, 64), (16, 1, 12, 64)],
                         ids=["chunk16", "mixed_step_slots"])
@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.int8],
                         ids=["fp32_pool", "int8_pool"])
@pytest.mark.parametrize("layer", [None, 5],
                         ids=["one_layers_pool", "stacked_flat_pools"])
def test_ragged_paged_attention_compiles_for_v5e(one_chip, pool_dtype,
                                                 q_shape, layer):
    """`stacked_flat_pools` is what the engine hands the kernel: its
    whole `[layers, N, bs, H * D]` pools and the layer to read."""
    from paddle_tpu.kernels.paged_attention import \
        ragged_paged_attention_pallas
    b = q_shape[0]
    stacked = layer is not None
    pool = _sds((12, 1024, 16, 768) if stacked else (1024, 16, 12, 64),
                pool_dtype)
    avals = [_sds(q_shape, jnp.float32), pool, pool,
             _sds((b, 64), jnp.int32), _sds((b,), jnp.int32),
             _sds((b,), jnp.int32)]
    if pool_dtype == jnp.int8:
        scales = _sds((12, 1024, 16, 12) if stacked else (1024, 16, 12),
                      jnp.float32)

        def fn(q, kp, vp, tables, q_lens, ctx_lens, ks, vs):
            return ragged_paged_attention_pallas(
                q, kp, vp, tables, q_lens, ctx_lens, interpret=False,
                k_scales=ks, v_scales=vs, layer=layer)
        avals += [scales, scales]
    else:
        fn = functools.partial(ragged_paged_attention_pallas,
                               interpret=False, layer=layer)
    _compile(fn, one_chip, *avals)


def _compile_mixed_step(cfg, sharding, params, pool, t, m, sw):
    """The generation engine's mixed step as `engine._build_fn` traces
    it for `cfg`'s model family (less the packing of its host arrays),
    compiled for the described chip: `t` slots of `m` table entries,
    `sw` sampler rows, both pools donated."""
    from paddle_tpu.generation import sample_tokens

    def mixed(params, kp, vp, tables, positions, tokens, slots, temps,
              tks, tps, seeds, steps):
        logits, kp, vp = cfg.forward_paged(params, kp, vp, tables,
                                           positions, tokens)
        with jax.named_scope("sampler"):
            return sample_tokens(logits[slots], temps, tks, tps, seeds,
                                 steps), kp, vp
    i32, f32 = jnp.int32, jnp.float32
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        (params, pool, pool, _sds((t, m), i32), _sds((t,), i32),
         _sds((t,), i32), _sds((sw,), i32), _sds((sw,), f32),
         _sds((sw,), i32), _sds((sw,), f32), _sds((sw,), i32),
         _sds((sw,), i32)))
    return jax.jit(mixed, donate_argnums=(1, 2)).lower(*args).compile()


def _computations(txt):
    """Compiled HLO text -> {computation: its instruction lines}, the
    entry computation under "ENTRY"."""
    comps, name = {}, None
    for line in txt.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _always_run(comps):
    """The computations the entry reaches WITHOUT entering a branch of
    a conditional: fusions, reducers, loop conditions and bodies."""
    seen, todo = set(), ["ENTRY"]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            for line in comps[c]:
                todo += re.findall(
                    r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", line)
    return seen


def _assert_sorts_inside_the_one_conditional(txt):
    """The sampler's batch-level branch survived the compiler: ONE
    `conditional`, the sampler's, run on every step; and every sort of
    the module (the filter path's) only behind a branch of it: none in
    the entry computation or a loop body."""
    comps = _computations(txt)
    always = _always_run(comps)
    conds = [ln for c in always for ln in comps[c] if " conditional(" in ln]
    assert len(conds) == txt.count(" conditional(") == 1
    assert "/sampler/" in conds[0]
    sorting = {c for c, lines in comps.items()
               if any(" sort(" in ln for ln in lines)}
    assert sorting, "the filter path has no sort left"
    assert not sorting & always, sorting & always


@pytest.mark.parametrize("form", ["reference", "pallas"])
def test_gpt2_mixed_step_sorts_only_inside_the_sampler_branch_for_v5e(
        one_chip, monkeypatch, form):
    """GPT-2 small's whole mixed step at the sizes of the benchmark's
    cell `gpt2_124m_chat_c32` (40 slots, 32 sampler rows over the
    vocabulary of 50,257, a float32 pool of 32,768 tokens): the sorts
    of `[32, 50257]` lie behind the sampler's conditional, both pools
    are aliased, and in the form a TPU resolves every layer's
    attention is one kernel call over the pool where it lies."""
    from paddle_tpu.generation import DecoderConfig, init_params
    from paddle_tpu.kernels import paged_attention as pa
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    cfg = DecoderConfig(vocab_size=50257, hidden=768, layers=12, heads=12,
                        max_seq_len=1024)
    with pa.kernel_form(form):
        compiled = _compile_mixed_step(
            cfg, one_chip,
            jax.eval_shape(lambda: init_params(cfg, seed=0)),
            _sds((12, 2048, 16, 768), jnp.float32), t=40, m=1024 // 16,
            sw=32)
    txt = compiled.as_text()
    _assert_sorts_inside_the_one_conditional(txt)
    assert txt.count("tpu_custom_call") == (12 if form == "pallas" else 0)
    made = re.findall(r"= f32\[12,2048,16,768\]\S* ([\w\-]+)\(", txt)
    assert set(made) <= {"parameter", "get-tuple-element", "fusion",
                         "scatter", "dynamic-update-slice"}, set(made)
    assert re.search(r"= \(f32\[32,50257\][^=]* sort\(", txt)
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * 12 * 2048 * 16 * 768 * 4


# The grouped kernel alone at BOTH serve cells' geometry, with the G and
# the fast memory its shapes derive: `gpt2_124m_chat_c32` (float32 rows
# of 768, 40 slots of 64 table entries, a static layer) and
# `ouro_2_6b_reason_c16` (bfloat16 rows of 2,048, 24 slots of 32
# entries, the layer a traced scalar).
@pytest.mark.parametrize("pool,slots,entries,heads,traced,g,vmem", [
    (_sds((12, 2048, 16, 768), jnp.float32), 40, 64, 12, False,
     8, 3 * 2 ** 19),
    (_sds((192, 320, 16, 2048), jnp.bfloat16), 24, 32, 16, True,
     8, 2 * 2 ** 20)], ids=["gpt2_124m_chat_c32", "ouro_2_6b_reason_c16"])
def test_grouped_kernel_compiles_at_the_cells_geometry_for_v5e(
        one_chip, pool, slots, entries, heads, traced, g, vmem):
    from paddle_tpu.kernels import paged_attention as pa
    bs, width = pool.shape[2:]
    row_bytes = width * pool.dtype.itemsize
    assert pa.blocks_per_step(bs, row_bytes, entries) == g
    # K and V tiles of G blocks, two buffers each
    assert 4 * g * bs * row_bytes == vmem <= pa._KV_VMEM_BUDGET

    def attend(q, kp, vp, tables, ctx, layer):
        return pa.paged_attention_pallas(
            q, kp, vp, tables, ctx, interpret=False,
            layer=layer if traced else 7)
    txt = _compile(attend, one_chip,
                   _sds((slots, heads, width // heads), jnp.float32),
                   pool, pool, _sds((slots, entries), jnp.int32),
                   _sds((slots,), jnp.int32), _sds((), jnp.int32))
    assert txt.count("tpu_custom_call") == 1
    # the pools are read where they lie: nothing pool-shaped is made
    shape = ",".join(map(str, pool.shape))
    assert not re.search(r"= \w+\[%s\]\S* (?!parameter)" % shape, txt)


def test_the_form_follows_the_backend_and_rides_the_fingerprint(
        monkeypatch):
    """The route: `resolved_form()` answers from the backend (the
    kernel on a TPU, the reference form elsewhere), `kernel_form(...)`
    and the engine's `kernel=` pin it, and the form the engine
    resolved is in its programs' compile key."""
    from paddle_tpu.core import program_accounting
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       init_params)
    from paddle_tpu.kernels import paged_attention as pa
    cfg = DecoderConfig(vocab_size=64, hidden=32, layers=2, heads=2,
                        max_seq_len=64)
    params = init_params(cfg, 0)

    def engine(**kw):
        return GenerationEngine(cfg, params, decode_width=2,
                                num_blocks=16, **kw)
    assert pa.resolved_form() == "reference"           # XLA:CPU
    assert engine().kernel == "reference"
    with pa.kernel_form("pallas"):
        assert pa.resolved_form() == "pallas"
        assert engine().kernel == "pallas"
        assert engine(kernel="reference").kernel == "reference"
    with monkeypatch.context() as on_a_tpu:
        on_a_tpu.setattr(jax, "default_backend", lambda: "tpu")
        assert pa.resolved_form() == "pallas"
        assert engine().kernel == "pallas"
        assert engine(kernel="reference").kernel == "reference"
        with pa.kernel_form("reference"):
            assert pa.resolved_form() == "reference"
    with pytest.raises(ValueError, match="unknown paged-attention"):
        engine(kernel="mosaic")
    metas = []
    real = program_accounting.accounted
    monkeypatch.setattr(
        program_accounting, "accounted",
        lambda jitted, avals, *, tag, key="", meta=None:
        metas.append((meta["kern"], key)) or
        real(jitted, avals, tag=tag, key=key, meta=meta))
    for form in ("reference", "pallas"):
        engine(kernel=form)._get_fn("cow")
    assert [m[0] for m in metas] == ["reference", "pallas"]
    assert metas[0][1] != metas[1][1]


# The looped family (generation/looped.py) at the sizes of the benchmark's
# cell `ouro_2_6b_reason_c16`: a bfloat16 pool of 192 cache layers of
# rows 16 x 128, 320 blocks of 16, 24 slots a step, and the cache layer
# a TRACED scalar (`pass * 48 + layer`, from inside the layer loop).
_LOOPED_POOL = _sds((192, 320, 16, 2048), jnp.bfloat16)


@pytest.mark.parametrize("form", ["reference", "pallas"])
def test_paged_attention_takes_a_traced_layer_for_v5e(one_chip, form):
    from paddle_tpu.kernels import paged_attention as pa
    fn = {"reference": pa.paged_attention_reference,
          "pallas": functools.partial(pa.paged_attention_pallas,
                                      interpret=False)}[form]

    def attend(q, kp, vp, tables, ctx, layer):
        return fn(q, kp, vp, tables, ctx, layer=layer)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in (_sds((24, 16, 128), jnp.float32), _LOOPED_POOL,
                      _LOOPED_POOL, _sds((24, 32), jnp.int32),
                      _sds((24,), jnp.int32), _sds((), jnp.int32))]
    txt = jax.jit(attend).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in txt) == (form == "pallas")
    # the pools are read where they lie: nothing pool-shaped is made
    assert not re.search(r"= bf16\[192,320,16,2048\]\S* (?!parameter)",
                         txt)


@pytest.mark.parametrize("form", ["reference", "pallas"])
def test_looped_mixed_step_compiles_whole_for_v5e(one_chip, monkeypatch,
                                                  form):
    """The cell's whole step (48 layers x 4 passes, the published
    widths, bfloat16 weights and pools, the sampler) as the engine
    jits it: ONE loop body inside two nested loops, the sampler's
    sorts behind its one conditional, each pool aliased to its output
    and never copied, weights + pools + temporaries inside the chip's
    memory."""
    import json
    import os
    from paddle_tpu.generation import looped
    from paddle_tpu.kernels import paged_attention as pa
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = json.load(open(os.path.join(root, "benchmark", "configs",
                                      "ouro_2_6b.json")))
    eng = src["engine"]
    cfg = looped.LoopedDecoderConfig.from_source(src, eng["max_context"])
    params = {k: _sds(s, jnp.bfloat16)
              for k, (s, _) in looped.leaf_shapes(cfg).items()}
    assert _LOOPED_POOL.shape == (cfg.kv_layers,
                                  eng["kv_pool_tokens"] // 16, 16,
                                  cfg.kv_row)
    with pa.kernel_form(form):
        compiled = _compile_mixed_step(
            cfg, one_chip, params, _LOOPED_POOL,
            t=eng["decode_width"] + 8, m=eng["max_context"] // 16,
            sw=eng["decode_width"])
    txt = compiled.as_text()
    assert txt.count(" while(") == 2
    _assert_sorts_inside_the_one_conditional(txt)
    assert txt.count("tpu_custom_call") == (1 if form == "pallas" else 0)
    head = txt.splitlines()[0]
    alias = head[head.index("input_output_alias"):]
    alias = alias[:alias.index("}, entry_computation_layout")]
    assert len(re.findall(r"\(\d+, \{\}", alias)) == 2, alias
    made = re.findall(r"= bf16\[192,320,16,2048\]\S* ([\w\-]+)\(", txt)
    assert set(made) <= {"parameter", "get-tuple-element", "fusion",
                         "scatter", "dynamic-update-slice"}, set(made)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 192 * 320 * 16 * 2048 * 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


# The expert family (generation/moe_window.py) at the sizes of the
# benchmark's cell `k_exaone_236b_reason_c128`: 7.42 GB of bfloat16
# weights, a bfloat16 pool of 5 cache layers of rows 8 x 128 under 64
# query heads, 10,240 blocks of 16, 136 slots a step, the cache layer
# AND the window traced scalars from inside the layer loop.
_EXPERT_POOL = _sds((5, 10240, 16, 1024), jnp.bfloat16)


@pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
def test_grouped_heads_and_a_traced_window_compile_for_v5e(one_chip,
                                                           window):
    """The kernel alone at the cell's geometry: a key-value row of 8
    heads under 64 query heads, G = 8 blocks a loop step (2 MiB of
    tiles), the window a fifth prefetched scalar; the pools are read
    where they lie."""
    from paddle_tpu.kernels import paged_attention as pa
    assert pa.blocks_per_step(16, 2048, 80) == 8

    def attend(q, kp, vp, tables, ctx, layer, win):
        return pa.paged_attention_pallas(
            q, kp, vp, tables, ctx, interpret=False, layer=layer,
            window=None if window is None else win)
    txt = _compile(attend, one_chip, _sds((136, 64, 128), jnp.float32),
                   _EXPERT_POOL, _EXPERT_POOL, _sds((136, 80), jnp.int32),
                   _sds((136,), jnp.int32), _sds((), jnp.int32),
                   _sds((), jnp.int32))
    assert txt.count("tpu_custom_call") == 1
    assert not re.search(r"= bf16\[5,10240,16,1024\]\S* (?!parameter)", txt)


@pytest.mark.parametrize("form", ["reference", "pallas"])
def test_expert_mixed_step_compiles_whole_for_v5e(one_chip, monkeypatch,
                                                  form):
    """The cell's whole step (the dense layer and four sparse layers at
    the published widths, 16 of 128 experts held, bfloat16 weights and
    pools, the routing counts, the sampler) as the engine jits it: the
    sparse layers ONE loop body, the grouped expert products two kernel
    calls over the experts' leaves WHERE THEY LIE (a layer's slice of
    them, 805 MB and 403 MB, is never copied out: temporaries of 23 MB,
    stated below), each pool aliased to its output and never copied,
    weights + pools + temporaries inside the chip's memory."""
    import json
    import os
    from paddle_tpu.generation import moe_window, sample_tokens
    from paddle_tpu.kernels import paged_attention as pa
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = json.load(open(os.path.join(root, "benchmark", "configs",
                                      "k_exaone_236b.json")))
    eng, held = src["engine"], src["experts_held"]
    cfg = moe_window.ExpertDecoderConfig.from_source(
        src, eng["max_context"], (held["first"], held["count"]))
    params = {k: _sds(s, jnp.bfloat16)
              for k, (s, _) in moe_window.leaf_shapes(cfg).items()}
    assert _EXPERT_POOL.shape == (cfg.kv_layers,
                                  eng["kv_pool_tokens"] // 16, 16,
                                  cfg.kv_row)
    t, m, sw = eng["decode_width"] + 8, eng["max_context"] // 16, \
        eng["decode_width"]

    def mixed(params, kp, vp, tables, positions, tokens, slots, temps,
              tks, tps, seeds, steps):
        logits, kp, vp, loads = cfg.forward_paged(
            params, kp, vp, tables, positions, tokens,
            live=tables[:, 0] != 0)
        with jax.named_scope("sampler"):
            nxt = sample_tokens(logits[slots], temps, tks, tps, seeds,
                                steps)
        return jnp.concatenate([nxt, loads.reshape(-1)]), kp, vp
    i32, f32 = jnp.int32, jnp.float32
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, _EXPERT_POOL, _EXPERT_POOL, _sds((t, m), i32),
         _sds((t,), i32), _sds((t,), i32), _sds((sw,), i32),
         _sds((sw,), f32), _sds((sw,), i32), _sds((sw,), f32),
         _sds((sw,), i32), _sds((sw,), i32)))
    with pa.kernel_form(form):
        compiled = jax.jit(mixed, donate_argnums=(1, 2)).lower(
            *args).compile()
    txt = compiled.as_text()
    # the four sparse layers are one loop (the one dense layer's loop of
    # a single trip is inlined by the compiler)
    assert txt.count(" while(") == 1
    # ONE conditional, the sampler's batch-level branch (this family
    # sorts outside it too: the router's choice and the pairs by expert)
    assert txt.count(" conditional(") == 1
    # the two grouped products and their metadata, and in the Pallas
    # form the attention of the dense layer and of the loop's body
    assert txt.count("tpu_custom_call") == (5 if form == "pallas" else 3)
    assert txt.count("ragged-dot-none") >= 2
    head = txt.splitlines()[0]
    alias = head[head.index("input_output_alias"):]
    alias = alias[:alias.index("}, entry_computation_layout")]
    assert len(re.findall(r"\(\d+, \{\}", alias)) == 2, alias
    made = re.findall(r"= bf16\[5,10240,16,1024\]\S* ([\w\-]+)\(", txt)
    assert set(made) <= {"parameter", "get-tuple-element", "fusion",
                         "scatter", "dynamic-update-slice"}, set(made)
    # nothing of a layer's experts' size is made: they are read in place
    assert not re.search(r"= bf16\[16,(6144,4096|2048,6144)\]\S* "
                         r"(?!bitcast|parameter|get-tuple-element)", txt)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 5 * 10240 * 16 * 1024 * 2
    weights = 2 * sum(math.prod(s.shape) for s in params.values())
    assert abs(weights / 1e9 - 7.42) < 0.01
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    if form == "pallas":
        assert mem.temp_size_in_bytes < 64 * 2 ** 20


# The latent family (generation/mla_moe.py) at the sizes of the benchmark's
# cell `kimi_k2_6_agent_c96`: 6.99 GB of bfloat16 weights, ONE bfloat16 pool of
# 5 cache layers of latent rows (512 + 64, in 640 lanes), 61,440 blocks of 16,
# 352 slots a step, tables of 640 entries, the cache layer a traced scalar.
_LATENT_POOL = _sds((5, 61440, 16, 640), jnp.bfloat16)


def test_latent_kernel_compiles_at_the_cells_geometry_for_v5e(one_chip):
    """The kernel alone: 64 heads of a slot against one row, G = 32 blocks
    a loop step (1.3 MB of rows, two buffers), tiles of Q = 8 slots (512
    query rows); the pool is read where it lies."""
    from paddle_tpu.kernels import latent_attention as la
    assert la.blocks_per_step(16, 1280, 640) == 32
    assert la.slots_per_tile(64, 16, 1280, 640, 512, 352) == 8

    def attend(q, pool, tables, ctx, layer):
        return la.latent_attention_pallas(q, pool, tables, ctx, 0.1, layer,
                                          512, interpret=False)
    txt = _compile(attend, one_chip, _sds((352, 64, 640), jnp.float32),
                   _LATENT_POOL, _sds((352, 640), jnp.int32),
                   _sds((352,), jnp.int32), _sds((), jnp.int32))
    assert txt.count("tpu_custom_call") == 1
    assert not re.search(r"= bf16\[5,61440,16,640\]\S* (?!parameter)", txt)


def test_latent_mixed_step_compiles_whole_for_v5e(one_chip, monkeypatch):
    """The cell's whole step in the Pallas form (the dense layer and four
    sparse layers at the published widths, 12 of 384 experts held, the
    routing counts and the kernel's tile counts, the sampler) as the
    engine jits it: the sparse layers ONE loop body, the pool aliased to
    its output and never copied, weights + pool + temporaries inside the
    chip's memory."""
    import json
    import os
    from paddle_tpu.generation import mla_moe, sample_tokens
    from paddle_tpu.kernels import paged_attention as pa
    monkeypatch.setattr(pa, "_use_interpret", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = json.load(open(os.path.join(root, "benchmark", "configs",
                                      "kimi_k2_6.json")))
    eng, held = src["engine"], src["experts_held"]
    cfg = mla_moe.LatentDecoderConfig.from_source(
        src, eng["max_context"], (held["first"], held["count"]))
    params = {k: _sds(s, jnp.bfloat16)
              for k, (s, _) in mla_moe.leaf_shapes(cfg).items()}
    assert _LATENT_POOL.shape == (cfg.kv_layers,
                                  eng["kv_pool_tokens"] // 16, 16,
                                  cfg.kv_row)
    t, m, sw = eng["token_budget"], eng["max_context"] // 16, \
        eng["decode_width"]

    def mixed(params, pool, tables, positions, tokens, slots, temps, tks,
              tps, seeds, steps):
        logits, pool, stats = cfg.forward_paged(
            params, pool, tables, positions, tokens,
            live=tables[:, 0] != 0)
        with jax.named_scope("sampler"):
            nxt = sample_tokens(logits[slots], temps, tks, tps, seeds,
                                steps)
        assert stats.shape == (cfg.step_stats_len,)
        return jnp.concatenate([nxt, stats]), pool
    i32, f32 = jnp.int32, jnp.float32
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, _LATENT_POOL, _sds((t, m), i32), _sds((t,), i32),
         _sds((t,), i32), _sds((sw,), i32), _sds((sw,), f32),
         _sds((sw,), i32), _sds((sw,), f32), _sds((sw,), i32),
         _sds((sw,), i32)))
    with pa.kernel_form("pallas"):
        compiled = jax.jit(mixed, donate_argnums=(1,)).lower(
            *args).compile()
    txt = compiled.as_text()
    assert txt.count(" while(") == 1
    # the latent kernel of the dense layer and of the loop's body, the two
    # grouped products and their metadata
    assert txt.count("tpu_custom_call") == 5
    head = txt.splitlines()[0]
    alias = head[head.index("input_output_alias"):]
    alias = alias[:alias.index("}, entry_computation_layout")]
    assert len(re.findall(r"\(\d+, \{\}", alias)) == 1, alias
    made = re.findall(r"= bf16\[5,61440,16,640\]\S* ([\w\-]+)\(", txt)
    assert set(made) <= {"parameter", "get-tuple-element", "fusion",
                         "scatter", "dynamic-update-slice"}, set(made)
    mem = compiled.memory_analysis()
    weights = 2 * sum(math.prod(s.shape) for s in params.values())
    assert weights == 2 * 3_496_763_904
    assert mem.alias_size_in_bytes >= 5 * 61440 * 16 * 640 * 2
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 15.75 * 2 ** 30
    assert mem.temp_size_in_bytes < 256 * 2 ** 20
