"""Paged LATENT attention: multi-head latent attention (MLA) in its
absorbed form over a paged pool of latent rows (docs/generation.md,
"Model families": `generation/mla_moe.py` is the family that calls it).

A row of the pool is one position's `[c | k_rope]`: the normed
key-value latent (`value_width` lanes) and the rotated key part that
every head shares, zeros after them up to a whole number of 128-lane
tiles (576 -> 640 at the published widths: the TPU's HBM layout pads
the minor axis so anyway, and Mosaic copies no narrower slice of a
padded axis). The rows of all heads are ONE row: the keys and the
values of head h are up-projections of `c` that the caller has folded
into the query and into the output (`latent_absorb`, `latent_out`),
so the attention is an MQA shape, every query head of a slot against
the same rows:

    s_h,j = q_h . row_j * sm_scale        (q_h = [q_lat_h | q_rope_h])
    out_h = sum_j softmax(s_h)_j row_j[:value_width]

Layouts: q `[B, H, R]` (R the row's width, zeros where the row's
are), the engine's stacked pool
`[layers, N, block_size, R]` with `layer` an int or a traced scalar
(read where it lies, no slice of the array the step updates in
place), block_tables `[B, max_blocks]` int32, ctx_lens `[B]` int32 the
VISIBLE count (the slot's position + 1). Returns `[B, H, value_width]`
float32.

Two forms, picked as `paged_attention`'s are (`resolved_form()`: the
kernel on a TPU, the reference form elsewhere; `kernel_form(...)` and
the engine's `kernel=` pin either):

- "reference": gather every slot's whole table and attend in plain
  XLA at float32. The CPU's parity oracle; its cost follows the
  table's width.
- "pallas": a grid step a slot and a loop over the slot's LIVE blocks
  only, G at a time: the pool stays in HBM, each live block is one
  async copy into fast memory, the next group in flight while this one
  is attended over. Each copied block serves the scores AND the values
  of all the slot's heads. The products take their operands in the
  pool's dtype (the query and the probabilities rounded to it) and
  accumulate in float32; the running max, sum and accumulator are
  float32 scratch carried across the groups.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa
from .paged_attention import NEG_INF

# fast memory the rows of a loop step may take, two buffers (the group
# computed on and the group in flight)
_ROW_VMEM_BUDGET = 4 * 1024 * 1024
# a loop step's positions: a score row of 64 heads by 512 positions is
# 128 KB of float32
_MAX_STEP_TOKENS = 512


def blocks_per_step(block_size: int, row_bytes: int,
                    max_blocks: int) -> int:
    """G, the pool blocks one step of the kernel's loop attends over:
    the largest power of two whose rows, two buffers, fit
    `_ROW_VMEM_BUDGET`, at most `_MAX_STEP_TOKENS` positions and at
    most the table's width (32 blocks of 16 bfloat16 rows of 640: 1.3
    MB of buffers)."""
    g = _ROW_VMEM_BUDGET // (2 * block_size * row_bytes)
    g = min(g, _MAX_STEP_TOKENS // block_size, max_blocks)
    return 1 << (max(g, 1).bit_length() - 1)


def latent_attention_reference(q, pool, block_tables, ctx_lens,
                               sm_scale: float, layer, value_width: int):
    """Gather through the tables and attend, float32 at precision
    `highest`: `[B, H, value_width]`."""
    b, _, r = q.shape
    bs = pool.shape[2]
    m = block_tables.shape[1]
    rows = pool[layer, block_tables].reshape(b, m * bs, r).astype(
        jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bhr,blr->bhl", q.astype(jnp.float32), rows,
                   precision=hi) * sm_scale
    pos = jnp.arange(m * bs, dtype=jnp.int32)
    visible = pos[None, None, :] < ctx_lens[:, None, None]
    s = jnp.where(visible, s, NEG_INF)
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.einsum("bhl,blc->bhc", p, rows[..., :value_width],
                      precision=hi)


def _kernel(tables_ref, lens_ref, layer_ref, q_ref, pool_hbm, o_ref, buf,
            sems, slot_ref, acc_ref, m_ref, l_ref, *, block_size, group,
            sm_scale, value_width):
    """Grid (B,): one grid step a slot, and inside it a loop over the
    slot's live groups of G table entries (entries below `ceil(ctx /
    block_size)`): nothing is run and nothing copied for the rest of
    the table. A parked slot (trash block, one visible position) costs
    one block."""
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    g_, bs = group, block_size
    max_blocks = tables_ref.shape[1]
    t = g_ * bs
    width = buf.shape[-1]
    heads = acc_ref.shape[0]
    lyr = layer_ref[0]

    def live_blocks(bi):
        return jnp.clip((lens_ref[bi] + bs - 1) // bs, 1, max_blocks)

    def copies(bi, gi, buf_i, go):
        """Start (or wait for) the copies of group `gi` of slot `bi`
        into buffer `buf_i`: one a live block."""
        n = live_blocks(bi)
        for g in range(g_):
            @pl.when(gi * g_ + g < n)
            def _():
                # waiting needs the copy's shape only, not its source
                blk = tables_ref[bi, gi * g_ + g] if go == "start" else 0
                cp = pltpu.make_async_copy(pool_hbm.at[lyr, blk],
                                           buf.at[buf_i, g],
                                           sems.at[buf_i])
                cp.start() if go == "start" else cp.wait()

    @pl.when(b == 0)
    def _first():
        # lanes of a buffer no copy has reached are masked, but a
        # masked p of 0.0 times whatever fast memory held is not 0.0
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        copies(0, 0, 0, "start")

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = (q_ref[0].astype(jnp.float32) * sm_scale).astype(buf.dtype)
    ctx = lens_ref[b]
    n_groups = (live_blocks(b) + g_ - 1) // g_

    def attend(gi, buf_i):
        more = gi + 1 < n_groups

        @pl.when(jnp.where(more, b, b + 1) < nb)
        def _prefetch():
            copies(jnp.where(more, b, b + 1), jnp.where(more, gi + 1, 0),
                   1 - buf_i, "start")
        copies(b, gi, buf_i, "wait")
        rows = buf[buf_i].reshape(t, width)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = gi * t + jax.lax.broadcasted_iota(jnp.int32, (heads, t), 1)
        mask = pos < ctx
        s = jnp.where(mask, s, NEG_INF)                  # [H, T]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(p.astype(rows.dtype), rows[:, :value_width],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        return 1 - buf_i

    slot_ref[0] = jax.lax.fori_loop(0, n_groups, attend, slot_ref[0])
    l = l_ref[...]
    o_ref[0] = (acc_ref[...] / jnp.where(l <= 0.0, 1.0, l)).astype(
        o_ref.dtype)


def latent_attention_pallas(q, pool, block_tables, ctx_lens,
                            sm_scale: float, layer, value_width: int,
                            interpret: Optional[bool] = None):
    """The blocked kernel (`_kernel`); interpret mode off the TPU."""
    if interpret is None:
        interpret = _pa._use_interpret()
    b, h, r = q.shape
    bs = pool.shape[2]
    g = blocks_per_step(bs, r * pool.dtype.itemsize, block_tables.shape[1])
    idx = lambda bi, *_: (bi, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # block_tables, ctx_lens, layer
        grid=(b,),
        in_specs=[pl.BlockSpec((1, h, r), idx),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, h, value_width), idx),
        scratch_shapes=[
            pltpu.VMEM((2, g, bs, r), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),             # the buffer in use
            pltpu.VMEM((h, value_width), jnp.float32),   # acc
            pltpu.VMEM((h, 1), jnp.float32),         # running max
            pltpu.VMEM((h, 1), jnp.float32),         # running denom
        ])
    kern = functools.partial(_kernel, block_size=bs, group=g,
                             sm_scale=sm_scale, value_width=value_width)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), jnp.float32),
        # the copies of a slot's first group start in the slot before
        # it: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_attention",
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pool)


def latent_attention(q, pool, block_tables, ctx_lens, *, sm_scale: float,
                     layer, value_width: int):
    """Absorbed latent attention over the paged pool, in the form
    `resolved_form()` answers, under ONE device-trace name in either
    form: `latent_attention`."""
    with jax.named_scope("latent_attention"):
        if _pa.resolved_form() == "pallas":
            return latent_attention_pallas(q, pool, block_tables, ctx_lens,
                                           sm_scale, layer, value_width)
        return latent_attention_reference(q, pool, block_tables, ctx_lens,
                                          sm_scale, layer, value_width)
