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
- "pallas": ONE grid step and a loop over the slots' TILES. A tile is up
  to Q consecutive slots of one lane (`latent_tiles`: the same table row,
  each slot one position further; a prefill chunk's tokens, a draft
  chain), a decode or parked slot a tile of one. Inside a tile, a loop
  over the LIVE blocks of its last (longest) slot only, G at a time: the
  pool stays in HBM, each live block is one async copy into fast memory,
  the next group (of this tile or the next) in flight while this one is
  attended over. Each copied block serves the scores AND the values of
  all the tile's slots and heads, `[n x heads, R]` query rows, each row
  under its own slot's mask (which is also the causality inside a
  chunk). A tile of one runs the same loop on `heads` rows alone. The
  queries come in and the contexts go out by copies of their own, the
  next tile's queries in flight beside this tile's loop. The products
  take their operands in the pool's dtype (the query and the
  probabilities rounded to it) and accumulate in float32; the running
  max, sum and accumulator are float32 scratch carried across the
  groups.
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
# fast memory of a loop step with a tile's scores and accumulator in
# float32 beside the two row buffers
_TILE_VMEM_BUDGET = 4 * 1024 * 1024
# a tile's query rows (slots x heads) at most: a tile that a run's tail
# leaves short computes them all the same
_MAX_TILE_ROWS = 512


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


def slots_per_tile(heads: int, block_size: int, row_bytes: int,
                   max_blocks: int, value_width: int, slots: int) -> int:
    """Q, the slots a tile holds at most: the largest power of two whose
    score block (`heads` rows a slot by G x block_size positions) and
    accumulator (`value_width` a row), float32, fit `_TILE_VMEM_BUDGET`
    beside the two row buffers, at most `_MAX_TILE_ROWS` query rows and
    at most `slots` (64 heads by 512 positions and 512 lanes, beside 1.3
    MB of rows: 8)."""
    g = blocks_per_step(block_size, row_bytes, max_blocks)
    rows = 2 * g * block_size * row_bytes
    per_slot = heads * (g * block_size + value_width) * 4
    q = min((_TILE_VMEM_BUDGET - rows) // per_slot,
            _MAX_TILE_ROWS // heads, slots)
    return 1 << (max(q, 1).bit_length() - 1)


def latent_tiles(block_tables, ctx_lens, tile: int):
    """The tiles of a step's slots, `(first, count)` `[B]` int32 each:
    tile k holds slots first[k] .. first[k] + count[k] - 1, in slot
    order; count is 0 past the last tile. Slot i continues slot i - 1's
    run where their whole table rows are equal and it sees one position
    more; a run is cut into tiles of `tile` from its first slot. Two
    lanes never hold equal rows (a writer's block is its own), and a
    parked slot (the trash table, one visible position) never continues
    one."""
    b = ctx_lens.shape[0]
    cont = jnp.all(block_tables[1:] == block_tables[:-1], axis=1) \
        & (ctx_lens[1:] == ctx_lens[:-1] + 1)
    cont = jnp.concatenate([jnp.zeros((1,), bool), cont])
    idx = jnp.arange(b, dtype=jnp.int32)
    run_first = jax.lax.cummax(jnp.where(cont, 0, idx))
    first = jnp.nonzero((idx - run_first) % tile == 0, size=b,
                        fill_value=b)[0].astype(jnp.int32)
    return first, jnp.diff(first, append=jnp.int32(b))


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


def _kernel(tables_ref, lens_ref, meta_ref, first_ref, count_ref, q_hbm,
            pool_hbm, o_hbm, buf, qbuf, obuf, sems, qsems, osems, slot_ref,
            acc_ref, m_ref, l_ref, *, block_size, group, tile, value_width):
    """One grid step: a loop over the `meta_ref[1]` tiles, and inside
    each a loop over the live groups of G table entries of its last
    slot (entries below `ceil(ctx / block_size)`): nothing is run and
    nothing copied for the rest of the table. A parked slot (trash
    block, one visible position) costs one block. Tile k's queries land
    in `qbuf[k % 2]` and its contexts leave from `obuf[k % 2]`, whose
    copies out are waited for two tiles later."""
    g_, bs = group, block_size
    slots, max_blocks = tables_ref.shape
    heads = q_hbm.shape[1]
    t = g_ * bs
    width = buf.shape[-1]
    lyr, n_tiles = meta_ref[0], meta_ref[1]

    def last(k):
        return first_ref[k] + count_ref[k] - 1

    def live_blocks(k):
        return jnp.clip((lens_ref[last(k)] + bs - 1) // bs, 1, max_blocks)

    def copies(k, gi, buf_i, go):
        """Start (or wait for) the copies of group `gi` of tile `k` into
        buffer `buf_i`: one a live block of its last slot's."""
        n, row = live_blocks(k), last(k)
        for g in range(g_):
            @pl.when(gi * g_ + g < n)
            def _():
                # waiting needs the copy's shape only, not its source
                blk = tables_ref[row, gi * g_ + g] if go == "start" else 0
                cp = pltpu.make_async_copy(pool_hbm.at[lyr, blk],
                                           buf.at[buf_i, g],
                                           sems.at[buf_i])
                cp.start() if go == "start" else cp.wait()

    def slot_copies(k, go, out):
        """Start (or wait for) tile k's queries into `qbuf[k % 2]`, or
        (`out`) its contexts from `obuf[k % 2]`: one copy a slot."""
        s = k % 2
        for j in range(tile):
            @pl.when(j < count_ref[k])
            def _():
                i = first_ref[k] + j if go == "start" else 0
                rows = pl.ds(j * heads, heads)
                if out:
                    cp = pltpu.make_async_copy(obuf.at[s, rows], o_hbm.at[i],
                                               osems.at[s])
                else:
                    cp = pltpu.make_async_copy(q_hbm.at[i], qbuf.at[s, rows],
                                               qsems.at[s])
                cp.start() if go == "start" else cp.wait()

    def attend(k, n):
        """Tile k's `n` slots' rows (n 1 or `tile`; rows of slots past
        its count are masked whole and never copied out) over the live
        groups, into `obuf[k % 2]`."""
        s = k % 2
        r = n * heads
        if n == 1:
            ctx = lens_ref[first_ref[k]]
        else:
            # each query row sees its own slot's positions
            row = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
            ctx = jnp.zeros((r, 1), jnp.int32)
            for j in range(n):
                cj = jnp.where(j < count_ref[k], lens_ref[
                    jnp.minimum(first_ref[k] + j, slots - 1)], 0)
                ctx = jnp.where((row >= j * heads) & (row < (j + 1) * heads),
                                cj, ctx)
        acc_ref[:r] = jnp.zeros((r, value_width), jnp.float32)
        m_ref[:r] = jnp.full((r, 1), NEG_INF, jnp.float32)
        l_ref[:r] = jnp.zeros((r, 1), jnp.float32)
        q = qbuf[s, :r]
        n_groups = (live_blocks(k) + g_ - 1) // g_

        def group_step(gi, buf_i):
            more = gi + 1 < n_groups

            @pl.when(more | (k + 1 < n_tiles))
            def _prefetch():
                copies(jnp.where(more, k, k + 1), jnp.where(more, gi + 1, 0),
                       1 - buf_i, "start")
            copies(k, gi, buf_i, "wait")
            rows = buf[buf_i].reshape(t, width)
            sc = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            pos = gi * t + jax.lax.broadcasted_iota(jnp.int32, (r, t), 1)
            mask = pos < ctx
            sc = jnp.where(mask, sc, NEG_INF)               # [r, T]
            m_prev = m_ref[:r]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:r] = l_ref[:r] * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[:r] = m_new
            pv = jax.lax.dot_general(p.astype(rows.dtype),
                                     rows[:, :value_width],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[:r] = acc_ref[:r] * alpha + pv
            return 1 - buf_i

        slot_ref[0] = jax.lax.fori_loop(0, n_groups, group_step, slot_ref[0])
        l = l_ref[:r]
        obuf[s, :r] = acc_ref[:r] / jnp.where(l <= 0.0, 1.0, l)

    def tile_step(k, carry):
        slot_copies(k, "wait", out=False)

        @pl.when(k + 1 < n_tiles)
        def _():
            slot_copies(k + 1, "start", out=False)

        @pl.when(k >= 2)
        def _():
            slot_copies(k - 2, "wait", out=True)    # obuf[k % 2] is free
        if tile > 1:
            @pl.when(count_ref[k] > 1)
            def _():
                attend(k, tile)

        @pl.when(count_ref[k] == 1)
        def _():
            attend(k, 1)
        slot_copies(k, "start", out=True)
        return carry

    # lanes of a buffer no copy has reached are masked, but a masked p
    # of 0.0 times whatever fast memory held is not 0.0
    buf[...] = jnp.zeros_like(buf)
    slot_ref[0] = 0
    slot_copies(0, "start", out=False)
    copies(0, 0, 0, "start")
    jax.lax.fori_loop(0, n_tiles, tile_step, 0)

    @pl.when(n_tiles >= 2)
    def _():
        slot_copies(n_tiles - 2, "wait", out=True)
    slot_copies(n_tiles - 1, "wait", out=True)


def latent_attention_pallas(q, pool, block_tables, ctx_lens,
                            sm_scale: float, layer, value_width: int,
                            interpret: Optional[bool] = None):
    """The tiled kernel (`_kernel`); interpret mode off the TPU. The
    query is scaled in float32 and rounded to the pool's dtype before
    it is copied in."""
    if interpret is None:
        interpret = _pa._use_interpret()
    b, h, r = q.shape
    bs = pool.shape[2]
    m = block_tables.shape[1]
    row_bytes = r * pool.dtype.itemsize
    g = blocks_per_step(bs, row_bytes, m)
    tile = slots_per_tile(h, bs, row_bytes, m, value_width, b)
    tables = block_tables.astype(jnp.int32)
    lens = ctx_lens.astype(jnp.int32)
    first, count = latent_tiles(tables, lens, tile)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.sum(count > 0, dtype=jnp.int32)])
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_tables, ctx_lens, (layer, tiles), each tile's first
        # slot and its count
        num_scalar_prefetch=5,
        grid=(1,),
        in_specs=[hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((2, g, bs, r), pool.dtype),
            pltpu.VMEM((2, tile * h, r), pool.dtype),       # queries
            pltpu.VMEM((2, tile * h, value_width), jnp.float32),  # out
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),             # the buffer in use
            pltpu.VMEM((tile * h, value_width), jnp.float32),   # acc
            pltpu.VMEM((tile * h, 1), jnp.float32),  # running max
            pltpu.VMEM((tile * h, 1), jnp.float32),  # running denom
        ])
    kern = functools.partial(_kernel, block_size=bs, group=g, tile=tile,
                             value_width=value_width)
    qs = (q.astype(jnp.float32) * sm_scale).astype(pool.dtype)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_attention",
    )(tables, lens, meta, first, count, qs, pool)


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
