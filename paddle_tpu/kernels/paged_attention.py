"""Paged attention for autoregressive decode (docs/generation.md).

The single-token decode step of the generation engine attends over a
sequence whose K/V live scattered across a fixed block pool
(`[num_blocks, block_size, H, D]` per layer) instead of one contiguous
array — the "Ragged Paged Attention" shape (PAPERS.md): every sequence
owns an ordered *block table* of pool indices, and attention gathers
keys through the table, masking positions at or beyond the sequence's
current context length. Because the pool, the tables, and the decode
batch are all fixed-shape, the decode step compiles ONCE and every
mixed-length continuous batch reuses it.

Two execution paths, selected by FLAGS_paged_attention_kernel:

- "reference" (default): gather + masked softmax in plain XLA. This is
  the parity oracle — `attend_reference` here is the SAME function the
  generation model's full-context forward uses, so a paged decode
  step agrees with a full-context recompute of the same position to
  within rounding (masked lanes contribute exp(-1e30 - m) == 0.0
  exactly, and adding exact zeros never perturbs the reduction).
- "pallas": the blocked kernel below — grid over (batch, blocks),
  block tables scalar-prefetched so each grid step's BlockSpec
  index_map DMAs exactly one pool block into VMEM, online-softmax
  (m, l, acc) carried in VMEM scratch across the sequential grid.
  Interpret mode runs it on CPU; on TPU hardware the same structure is
  the Mosaic-ready seam (one block resident at a time, MXU dots, no
  [S] contiguous KV ever materialized).

Layouts: q `[B, H, D]` (one new token per sequence), pools
`[N, block_size, H, D]`, block_tables `[B, max_blocks]` int32,
ctx_lens `[B]` int32 (number of VISIBLE keys, i.e. the new token's
position + 1). Returns `[B, H, D]`. The engine passes its whole
stacked pools instead, `[layers, N, block_size, H * D]` with
`layer=` (an int, or a traced scalar inside a layer loop): every entry
point reads that layer's blocks where they lie, with no slice of the
array the step updates in place.

RAGGED entry (PR 10, chunked prefill): `ragged_paged_attention` takes
q `[B, Cq, H, D]` where row b carries `q_lens[b]` real queries — 1 for
a decode step, a chunk width for prefill — starting at absolute
position `ctx_lens[b]` (here ctx_lens counts the keys BEFORE the
chunk, not the visible total). Query j of row b sees pool positions
`<= ctx_lens[b] + j`: causal inside the chunk, full history before it.
The single-token functions above are the Cq == 1 specialization and
delegate here, so decode parity pins cover the ragged core by
construction.

VERIFY LANES (PR 14, speculative decoding): a decode lane carrying k
draft tokens is encoded exactly like a prefill chunk — k+1 adjacent
slots sharing the lane's block table at consecutive positions
ctx..ctx+k — so the causal chunk mask above IS the verify mask: slot j
sees the drafts before it (scattered this same call) and nothing past
its own position. That last property is also the rollback guarantee:
a REJECTED draft's K/V sits at a position strictly greater than every
accepted slot's, so no mask in this step or any later one exposes it
before the next step's feed overwrites that position. Same argument
covers the prefix cache's shared blocks: a consumer whose context
frontier is below a shared partial block's stale tail never has those
positions inside its mask.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

# finite "minus infinity", matching kernels/flash_attention.py: after
# the running-max subtraction exp(NEG_INF - m) underflows to exactly
# 0.0, so masked lanes are bitwise inert in every reduction
NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _inv_grid(pool_dtype) -> float:
    """1/GRID for a quantized pool's storage dtype — the dequant
    constant of the shared absmax scale contract (paddle_tpu/quant):
    stored * scale / GRID recovers the value. Derived from the pool
    itself so callers never thread a mode string into the kernel."""
    from ..quant import grid_for_dtype
    return 1.0 / grid_for_dtype(pool_dtype)


# ---------------------------------------------------------------------------
# shared masked-softmax attention core (prefill AND decode use this)
# ---------------------------------------------------------------------------

def attend_reference(q, k, v, mask, sm_scale):
    """Masked attention, fp32 accumulation: q `[B, H, Tq, D]`,
    k/v `[B, H, Tk, D]`, mask `[B, 1, Tq, Tk]` bool (True = visible).

    This one function is the numerics contract of the generation
    subsystem: the model's full-context prefill and the paged decode
    reference both route through it, so prefill/decode parity is
    structural rather than coincidental. Two deliberate choices make
    the parity BITWISE on XLA:CPU (tests/test_generation.py pins it):

    - scores and PV are broadcast-multiply + jnp.sum reductions, NOT
      dot_general. A GEMM (Tq=bucket prefill) and a GEMV (Tq=1 decode)
      accumulate the same dot product in different orders — measured
      1e-7 drift — while an explicit last-axis reduce lowers
      identically for both query shapes AND for padded-vs-exact Tk.
    - masked lanes score NEG_INF (finite): exp(NEG_INF - m) underflows
      to exactly 0.0, so padding lanes are bitwise inert in every sum,
      and a row with NO visible key (inactive decode lane) degrades to
      a finite uniform average instead of NaN."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # [B,H,Tq,Tk,D] -> sum over D
    s = jnp.sum(qf[:, :, :, None, :] * kf[:, :, None, :, :],
                axis=-1) * sm_scale
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    # [B,H,Tq,Tk,1] * [B,H,1,Tk,D] -> sum over Tk
    out = jnp.sum(p[..., None] * vf[:, :, None, :, :], axis=-2)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# reference paged path (ragged core + Cq == 1 decode specialization)
# ---------------------------------------------------------------------------

def ragged_paged_attention_reference(q, k_pool, v_pool, block_tables,
                                     q_lens, ctx_lens,
                                     sm_scale: Optional[float] = None,
                                     k_scales=None, v_scales=None,
                                     layer: Optional[int] = None):
    """Ragged gather-from-block-table attention in plain XLA.

    q `[B, Cq, H, D]`: row b holds `q_lens[b]` real queries at absolute
    positions `ctx_lens[b] .. ctx_lens[b] + q_lens[b] - 1` (the chunk's
    own K/V must already be scattered into the pool). Query j sees pool
    positions `<= ctx_lens[b] + j` — causal within the chunk, the full
    paged history before it. Rows `j >= q_lens[b]` are fully masked and
    come back as the finite uniform-average degradation of
    attend_reference (never NaN, never read by callers).

    The gather materializes each sequence's `[max_blocks * block_size]`
    logical KV view (masked positions hide stale or foreign blocks
    behind the table), then runs the shared attend_reference core with
    Tq == Cq — the same ops and reduction shapes as the full-context
    forward, which is what keeps the chunked path within rounding of a
    `forward_full` recompute (tests/test_kernels.py::
    test_chunked_prefill_mixed_batch_bitwise_vs_forward_full).

    QUANTIZED KV (ISSUE 15): int8/fp8 pools ride with per-token-per-head
    absmax scales `k_scales`/`v_scales` `[N, bs, H]` — the gather pulls
    stored values AND scales through the same block table and
    dequantizes (stored * scale / GRID) right at the softmax input, the
    XLA-fused analog of the in-loop dequant in the Pallas kernel below.
    `None` scales take the EXACT pre-quant expressions, keeping the
    fp32 path bitwise-identical.

    STACKED POOLS: with `layer` (an int, or a traced scalar inside a
    layer loop) the pools are the engine's whole arrays, `[layers, N, bs, H * D]` with the heads'
    two axes FLAT (scale pools `[layers, N, bs, H]`), and the gather
    indexes `(layer, block)` straight into them, so the caller never
    slices a layer's pool out of the array it updates in place
    (generation/model.py:forward_paged). The gathered `[B, L, H * D]`
    view is then read one head at a time, as `D`-wide slices of its
    minor axis, each through attend_reference with one head: the same
    products and the same reductions, head for head, and no
    `[.., H * D] -> [.., H, D]` reshape of the view, which on the TPU
    is a relayout of the whole gathered array (twice over, K and V of
    every layer: 28 of 39 ms a step, PERF.md PR 28)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    b, cq, h, d = q.shape
    bs = k_pool.shape[1 if layer is None else 2]
    m = block_tables.shape[1]
    pos = jnp.arange(m * bs, dtype=jnp.int32)
    qi = jnp.arange(cq, dtype=jnp.int32)
    # [B, Cq, L]: pool position visible to query j of row b
    visible = pos[None, None, :] <= \
        (ctx_lens[:, None] + qi[None, :])[:, :, None]
    live = (qi[None, :] < q_lens[:, None])[:, :, None]
    mask = (visible & live)[:, None, :, :]            # [B, 1, Cq, L]
    qt = jnp.transpose(q, (0, 2, 1, 3))               # [B, H, Cq, D]
    if layer is not None:
        out = _attend_stacked(qt, k_pool, v_pool, k_scales, v_scales,
                              layer, block_tables, mask, sm_scale)
        return jnp.transpose(out, (0, 2, 1, 3))
    if k_scales is None:
        # [B, M, bs, H, D] -> [B, H, M*bs, D]
        k = jnp.transpose(k_pool[block_tables], (0, 3, 1, 2, 4)
                          ).reshape(b, h, m * bs, d)
        v = jnp.transpose(v_pool[block_tables], (0, 3, 1, 2, 4)
                          ).reshape(b, h, m * bs, d)
    else:
        inv = _inv_grid(k_pool.dtype)
        kg = k_pool[block_tables].astype(jnp.float32) \
            * (k_scales[block_tables] * inv)[..., None]
        vg = v_pool[block_tables].astype(jnp.float32) \
            * (v_scales[block_tables] * inv)[..., None]
        k = jnp.transpose(kg, (0, 3, 1, 2, 4)).reshape(b, h, m * bs, d)
        v = jnp.transpose(vg, (0, 3, 1, 2, 4)).reshape(b, h, m * bs, d)
    out = attend_reference(qt, k, v, mask, sm_scale)
    return jnp.transpose(out, (0, 2, 1, 3))


def _attend_stacked(qt, k_pool, v_pool, k_scales, v_scales, layer,
                    block_tables, mask, sm_scale):
    """The reference attention over layer `layer` of the engine's
    stacked flat pools, one head at a time (see
    ragged_paged_attention_reference, STACKED POOLS): `[B, H, Cq, D]`."""
    b, h, _, d = qt.shape

    def view(pool):                                   # [B, L, width]
        return pool[layer, block_tables].reshape(b, mask.shape[-1], -1)
    kf, vf = view(k_pool), view(v_pool)
    if k_scales is not None:
        inv = _inv_grid(k_pool.dtype)
        ksc, vsc = view(k_scales) * inv, view(v_scales) * inv
    outs = []
    for i in range(h):
        k = kf[:, None, :, i * d:(i + 1) * d]         # [B, 1, L, D]
        v = vf[:, None, :, i * d:(i + 1) * d]
        if k_scales is not None:
            k = k.astype(jnp.float32) * ksc[:, None, :, i, None]
            v = v.astype(jnp.float32) * vsc[:, None, :, i, None]
        outs.append(attend_reference(qt[:, i:i + 1], k, v, mask,
                                     sm_scale))
    return jnp.concatenate(outs, axis=1)


def paged_attention_reference(q, k_pool, v_pool, block_tables, ctx_lens,
                              sm_scale: Optional[float] = None,
                              k_scales=None, v_scales=None,
                              layer: Optional[int] = None):
    """Single-token decode attention: the Cq == 1 specialization of the
    ragged path. ctx_lens here counts VISIBLE keys (position + 1), so
    the ragged call gets `ctx_lens - 1` keys-before-the-query and a
    q_len of 1 — `pos <= ctx - 1` is the same mask booleans as the
    historic `pos < ctx`, keeping this delegation bitwise-identical to
    the pre-ragged decode path."""
    ctx = jnp.asarray(ctx_lens)
    out = ragged_paged_attention_reference(
        q[:, None], k_pool, v_pool, block_tables,
        jnp.ones_like(ctx), ctx - 1, sm_scale,
        k_scales=k_scales, v_scales=v_scales, layer=layer)
    return out[:, 0]


# ---------------------------------------------------------------------------
# Pallas kernel: one pool block in VMEM per grid step
# ---------------------------------------------------------------------------

def _tile(ref, heads):
    """The resident pool block as float32 `[bs, H, D]`. A block of the
    engine's stacked pools arrives flat, `[1, bs, H * D]`
    (ragged_paged_attention_pallas, `layer`), and is split into heads
    here, in VMEM, by lane slices (Mosaic has no such reshape, and
    none of an int8 tile: hence the cast first); a `[1, bs, H, D]`
    block is taken as it is."""
    t = ref[0].astype(jnp.float32)
    if t.ndim == 3:
        return t
    d = t.shape[1] // heads
    return jnp.stack([t[:, i * d:(i + 1) * d] for i in range(heads)],
                     axis=1)


def _ragged_kernel(tables_ref, qlens_ref, lens_ref, layer_ref, q_ref,
                   k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   block_size, sm_scale, num_blocks):
    """Grid (B, max_blocks): sequential online-softmax over the
    sequence's blocks, Cq queries per row. tables/q_lens/ctx_lens (and
    the stacked pools' layer, which only the index maps read) arrive
    via scalar prefetch — the index maps already used tables_ref to
    pick this (k, v) block, so the body only handles the causal chunk
    mask and the (m, l, acc) recurrence carried per (head, query)."""
    b = pl.program_id(0)
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = lens_ref[b]
    qlen = qlens_ref[b]

    # blocks entirely past the chunk's last visible key (position
    # ctx + qlen - 1) contribute nothing; skipping the math (the DMA
    # already happened) keeps the scratch recurrence exact for ragged
    # lengths
    @pl.when(mi * block_size < ctx + qlen)
    def _body():
        q = q_ref[0].astype(jnp.float32) * sm_scale      # [Cq, H, D]
        k = _tile(k_ref, q.shape[1])                     # [bs, H, D]
        v = _tile(v_ref, q.shape[1])
        # batch over heads, contract D: [H, Cq, bs]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32)
        pos = mi * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((pos <= ctx + qi) & (qi < qlen), s, NEG_INF)
        m_prev = m_ref[...]                              # [H, Cq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[:, :, None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2)
        m_ref[...] = m_new
        # [H, Cq, bs] x [bs, H, D] -> [H, Cq, D]: batch over H
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :, None] + pv

    @pl.when(mi == num_blocks - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l <= 0.0, 1.0, l)
        o_ref[0] = jnp.transpose(acc_ref[...] / l_safe[:, :, None],
                                 (1, 0, 2)).astype(o_ref.dtype)


def _ragged_kernel_quant(tables_ref, qlens_ref, lens_ref, layer_ref,
                         q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, block_size, sm_scale,
                         num_blocks, inv_grid):
    """Quantized-KV twin of _ragged_kernel: the block's int8/fp8 K/V
    tile arrives in VMEM with its `[bs, H]` absmax scale rows (same
    tbl[bi, mi] index maps), and dequant (stored * scale / GRID) runs
    INSIDE the online-softmax loop — the fp32 KV never exists outside
    this block's VMEM residency, which is the whole HBM win."""
    b = pl.program_id(0)
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx = lens_ref[b]
    qlen = qlens_ref[b]

    @pl.when(mi * block_size < ctx + qlen)
    def _body():
        q = q_ref[0].astype(jnp.float32) * sm_scale      # [Cq, H, D]
        # in-loop dequant: [bs, H, D] stored * [bs, H, 1] scale/GRID
        k = _tile(k_ref, q.shape[1]) \
            * (ks_ref[0].astype(jnp.float32) * inv_grid)[:, :, None]
        v = _tile(v_ref, q.shape[1]) \
            * (vs_ref[0].astype(jnp.float32) * inv_grid)[:, :, None]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32)
        pos = mi * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((pos <= ctx + qi) & (qi < qlen), s, NEG_INF)
        m_prev = m_ref[...]                              # [H, Cq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[:, :, None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, :, None] + pv

    @pl.when(mi == num_blocks - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l <= 0.0, 1.0, l)
        o_ref[0] = jnp.transpose(acc_ref[...] / l_safe[:, :, None],
                                 (1, 0, 2)).astype(o_ref.dtype)


def ragged_paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                  q_lens, ctx_lens,
                                  sm_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None,
                                  k_scales=None, v_scales=None,
                                  layer: Optional[int] = None):
    """Blocked ragged kernel: same grid over (sequence, pool block) as
    the decode kernel, but each VMEM tile scores the whole Cq-wide
    chunk against one resident block, so prefill chunks and decode
    singles share one executable shape. Quantized pools (k_scales /
    v_scales given) route to the _ragged_kernel_quant twin — the fp32
    kernel is untouched so the quant-off executable stays identical.
    With `layer` the pools are the engine's stacked arrays, `[layers,
    N, bs, H * D]` with the heads flat (scales `[layers, N, bs, H]`):
    the index maps put `layer` in front of the table's block, the
    layer axis is squeezed out of the tile, and the kernel bodies
    split the flat `[1, bs, H * D]` block into heads (_tile). `layer`
    is a scalar-prefetch operand like the tables, so it may be a
    TRACED scalar: the looped family's layer loop
    (generation/looped.py) reads cache slot `pass * layers + layer`
    from inside a `lax.scan`, where an index map could not close over
    it."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _use_interpret()
    b, cq, h, d = q.shape
    bs = k_pool.shape[1 if layer is None else 2]
    m = block_tables.shape[1]

    def pool_spec(*tail):
        """One table-picked block of a pool: `(1, bs, *tail)` tiles."""
        zeros = (0,) * (1 + len(tail))
        if layer is None:
            return pl.BlockSpec(
                (1, bs) + tail,
                lambda bi, mi, tbl, qls, lens, lyr:
                (tbl[bi, mi],) + zeros)
        return pl.BlockSpec(
            (None, 1, bs) + tail,
            lambda bi, mi, tbl, qls, lens, lyr:
            (lyr[0], tbl[bi, mi]) + zeros)
    kv_spec = pool_spec(h, d) if layer is None else pool_spec(h * d)
    in_specs = [
        pl.BlockSpec((1, cq, h, d),
                     lambda bi, mi, tbl, qls, lens, lyr: (bi, 0, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [q, k_pool, v_pool]
    if k_scales is not None:
        # scale rows ride the SAME block-table index map as their
        # payload tile, one [bs, H] row set per resident block
        in_specs += [pool_spec(h), pool_spec(h)]
        operands += [k_scales, v_scales]
        kern = functools.partial(
            _ragged_kernel_quant, block_size=bs, sm_scale=sm_scale,
            num_blocks=m, inv_grid=_inv_grid(k_pool.dtype))
    else:
        kern = functools.partial(_ragged_kernel, block_size=bs,
                                 sm_scale=sm_scale, num_blocks=m)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # block_tables, q_lens, ctx_lens, layer
        grid=(b, m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, cq, h, d),
            lambda bi, mi, tbl, qls, lens, lyr: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, cq, d), jnp.float32),   # acc
            pltpu.VMEM((h, cq), jnp.float32),      # running max
            pltpu.VMEM((h, cq), jnp.float32),      # running denom
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, cq, h, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), q_lens.astype(jnp.int32),
      ctx_lens.astype(jnp.int32),
      jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1),
      *operands)


def paged_attention_pallas(q, k_pool, v_pool, block_tables, ctx_lens,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           k_scales=None, v_scales=None,
                           layer: Optional[int] = None):
    """Single-token decode kernel: Cq == 1 delegation to the ragged
    kernel (same visible-count ctx_lens convention as the reference
    specialization above)."""
    ctx = jnp.asarray(ctx_lens)
    out = ragged_paged_attention_pallas(
        q[:, None], k_pool, v_pool, block_tables,
        jnp.ones_like(ctx), ctx - 1, sm_scale, interpret,
        k_scales=k_scales, v_scales=v_scales, layer=layer)
    return out[:, 0]


# ---------------------------------------------------------------------------
# public entry: flag-routed seam (+ the autotune override)
# ---------------------------------------------------------------------------

# Trace-scoped kernel-form override (paddle_tpu/autotune.py): the
# dispatch policy's winning form must be bakeable into a compile
# WITHOUT flipping the process-global flag (two engines in one process
# may resolve different forms). The engine wraps its trace-time
# construction in kernel_form(...); the flag stays the default route
# and the compile-key story is unchanged — the engine puts the
# RESOLVED form into its program fingerprint meta (kern=..., v=4).
_FORM_OVERRIDE: Optional[str] = None


class kernel_form:
    """Context manager pinning the kernel form ("reference"|"pallas")
    for computations TRACED inside the block. None passes through to
    FLAGS_paged_attention_kernel."""

    __slots__ = ("form", "_prev")

    def __init__(self, form: Optional[str]):
        self.form = form

    def __enter__(self):
        global _FORM_OVERRIDE
        self._prev = _FORM_OVERRIDE
        if self.form is not None:
            _FORM_OVERRIDE = self.form
        return self

    def __exit__(self, *exc):
        global _FORM_OVERRIDE
        _FORM_OVERRIDE = self._prev
        return False


def resolved_form() -> str:
    """The kernel form the next trace will bake in: the active
    kernel_form override, else FLAGS_paged_attention_kernel."""
    if _FORM_OVERRIDE is not None:
        return _FORM_OVERRIDE
    from ..flags import get_flag
    return str(get_flag("FLAGS_paged_attention_kernel"))


def paged_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                    sm_scale: Optional[float] = None,
                    k_scales=None, v_scales=None,
                    layer: Optional[int] = None):
    """Decode-step attention over the paged KV pool. Routed by
    FLAGS_paged_attention_kernel (a lowering flag: it is baked into
    every generation compile key), subject to the kernel_form override
    above: "reference" is the parity path (the oracle's own attention
    core); "pallas" runs the blocked kernel (interpret mode off-TPU).
    k_scales/v_scales
    (quantized pools, paddle_tpu/quant) flow to the dequant-fused
    forms of both paths; None = the untouched fp32 path. `layer`
    (an int or a traced scalar) says the pools are the stacked
    `[layers, N, bs, ...]` arrays and picks the layer to read, in both
    forms without a slice. Pools may be float32, bfloat16 (read as
    they are and upcast where they are multiplied) or quantized."""
    mode = resolved_form()
    # ONE device-trace name for the gather and the attention over it,
    # in either form: a kernel change is read by the same metric
    with jax.named_scope("paged_attention"):
        if mode == "pallas":
            return paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                          ctx_lens, sm_scale,
                                          k_scales=k_scales,
                                          v_scales=v_scales, layer=layer)
        return paged_attention_reference(q, k_pool, v_pool, block_tables,
                                         ctx_lens, sm_scale,
                                         k_scales=k_scales,
                                         v_scales=v_scales, layer=layer)


def ragged_paged_attention(q, k_pool, v_pool, block_tables, q_lens,
                           ctx_lens, sm_scale: Optional[float] = None,
                           k_scales=None, v_scales=None,
                           layer: Optional[int] = None):
    """Mixed prefill+decode attention over the paged KV pool: q
    `[B, Cq, H, D]` with per-row true query length (1 = decode, chunk
    width = prefill). Routed by the same FLAGS_paged_attention_kernel
    seam (+ kernel_form override) as the decode entry; k_scales /
    v_scales select the quantized-KV dequant-fused forms."""
    mode = resolved_form()
    with jax.named_scope("paged_attention"):
        if mode == "pallas":
            return ragged_paged_attention_pallas(
                q, k_pool, v_pool, block_tables, q_lens, ctx_lens,
                sm_scale, k_scales=k_scales, v_scales=v_scales,
                layer=layer)
        return ragged_paged_attention_reference(
            q, k_pool, v_pool, block_tables, q_lens, ctx_lens, sm_scale,
            k_scales=k_scales, v_scales=v_scales, layer=layer)
