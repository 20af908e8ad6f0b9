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

Two execution paths. The backend selects one (`resolved_form()`: the
kernel on a TPU, the reference form elsewhere); `kernel_form(...)` and
the engine's `kernel=` argument pin either, and there is no flag:

- "reference": gather + masked softmax in plain XLA. This is
  the parity oracle — `attend_reference` here is the SAME function the
  generation model's full-context forward uses, so a paged decode
  step agrees with a full-context recompute of the same position to
  within rounding (masked lanes contribute exp(-1e30 - m) == 0.0
  exactly, and adding exact zeros never perturbs the reduction). It
  gathers every slot's WHOLE table: its cost follows the table's
  width, not the tokens held.
- "pallas": the blocked kernel below — a grid step a slot and inside
  it a loop over the slot's LIVE blocks, G at a time; block tables
  scalar-prefetched, the pools left in HBM and only live blocks
  copied into VMEM, one async copy each, the next group in flight
  while this one is attended over; online-softmax (m, l, acc) carried
  in VMEM scratch across the loop; float32 products on the MXU with
  the heads flat, as the pool stores them. Its cost follows the tokens a slot attends
  over. Interpret mode runs it on CPU for its own tests.

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
                                     layer: Optional[int] = None,
                                     window=None):
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
    every layer: 28 of 39 ms a step, PERF.md PR 28).

    WINDOW and GROUPED HEADS: see `paged_attention`. `window` masks
    (no gather is saved here: this form's cost follows the table);
    a STACKED pool's row of fewer heads than q has is read head
    `h // rep` (a single layer's `[N, bs, H, D]` pool holds q's heads)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    b, cq, h, d = q.shape
    bs = k_pool.shape[1 if layer is None else 2]
    m = block_tables.shape[1]
    pos = jnp.arange(m * bs, dtype=jnp.int32)
    qi = jnp.arange(cq, dtype=jnp.int32)
    # [B, Cq, L]: pool position visible to query j of row b
    qpos = (ctx_lens[:, None] + qi[None, :])[:, :, None]
    visible = pos[None, None, :] <= qpos
    if window is not None:
        w = jnp.asarray(window, jnp.int32)
        visible &= (w <= 0) | (pos[None, None, :] > qpos - w)
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
    ragged_paged_attention_reference, STACKED POOLS): `[B, H, Cq, D]`.
    A row of fewer heads than q has (grouped key-value heads) is read
    a key-value head at a time, by the `rep` query heads that share
    it; with `rep` 1 that is a head at a time, as it was."""
    b, h, _, d = qt.shape

    def view(pool):                                   # [B, L, width]
        return pool[layer, block_tables].reshape(b, mask.shape[-1], -1)
    kf, vf = view(k_pool), view(v_pool)
    rep = h * d // kf.shape[-1]     # query heads a key-value head
    if k_scales is not None:
        inv = _inv_grid(k_pool.dtype)
        ksc, vsc = view(k_scales) * inv, view(v_scales) * inv
    outs = []
    for i in range(h // rep):
        k = kf[:, None, :, i * d:(i + 1) * d]         # [B, 1, L, D]
        v = vf[:, None, :, i * d:(i + 1) * d]
        if k_scales is not None:
            k = k.astype(jnp.float32) * ksc[:, None, :, i, None]
            v = v.astype(jnp.float32) * vsc[:, None, :, i, None]
        outs.append(attend_reference(qt[:, i * rep:(i + 1) * rep], k, v,
                                     mask, sm_scale))
    return jnp.concatenate(outs, axis=1)


def paged_attention_reference(q, k_pool, v_pool, block_tables, ctx_lens,
                              sm_scale: Optional[float] = None,
                              k_scales=None, v_scales=None,
                              layer: Optional[int] = None, window=None):
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
        k_scales=k_scales, v_scales=v_scales, layer=layer, window=window)
    return out[:, 0]


# ---------------------------------------------------------------------------
# Pallas kernel: a slot per grid step, its LIVE blocks G at a time
# ---------------------------------------------------------------------------

# Fast memory the kernel may hold K and V tiles in: two buffers each
# (the group computed on and the group in flight). The number of pool
# blocks a step of the kernel's loop handles follows from it
# (blocks_per_step).
_KV_VMEM_BUDGET = 4 * 1024 * 1024
# a loop step's lanes, one vreg row of scores a head: beyond this a
# group only adds masked work for the contexts that end inside it
# (on the chip, the attention of one step's layers at the benchmark's
# two geometries: 1.96 / 1.58 / 1.62 / 1.95 ms and 11.7 / 9.4 / 9.8 /
# 15.8 ms at 64 / 128 / 256 / 512 lanes; PERF.md, PR 32)
_MAX_STEP_TOKENS = 128


def blocks_per_step(block_size: int, row_bytes: int,
                    max_blocks: int) -> int:
    """G, the pool blocks one step of the kernel's loop attends over:
    the largest power of two whose K and V tiles, two buffers each,
    fit `_KV_VMEM_BUDGET`, at most `_MAX_STEP_TOKENS` positions and at
    most the table's width. Derived from what the call can see (a
    row's bytes `H * D * itemsize`, the block size, the table) — no
    flag and no argument: 8 blocks of GPT-2's 48 KB (float32 rows of
    768) and of the looped family's 64 KB (bfloat16 rows of 2,048),
    1.5 and 2 MiB of tiles; the budget binds from rows of 8 KB on."""
    g = _KV_VMEM_BUDGET // (4 * block_size * row_bytes)
    g = min(g, _MAX_STEP_TOKENS // block_size, max_blocks)
    return 1 << (max(g, 1).bit_length() - 1)


def _pieces(x):
    """float32 `x` as three bfloat16 addends, largest first. The MXU
    multiplies bfloat16; three addends carry a float32's 24 bits, so
    products of such pieces summed in float32 are the float32 product
    (what `Precision.HIGHEST` does). A value that IS a bfloat16 (a
    bfloat16 or int8/fp8 pool's row) is its own one piece."""
    hi = x.astype(jnp.bfloat16)
    x = x - hi.astype(jnp.float32)
    mid = x.astype(jnp.bfloat16)
    return [hi, mid, (x - mid.astype(jnp.float32)).astype(jnp.bfloat16)]


def _dot_pieces(lhs3, rhs_pieces, contract_rhs):
    """sum over the piece pairs that matter of lhs_i . rhs_j, float32.
    `lhs3` holds the left side's pieces stacked along rows, `[3 *
    rows, C]`; piece j of the right side meets the first `3 - j` of
    them (the six products of a float32 x float32 at full precision,
    three where the right side is exact in bfloat16): one MXU pass per
    right piece, the left pieces riding as extra rows."""
    rows = lhs3.shape[0] // 3
    total = None
    for j, r in enumerate(rhs_pieces):
        n = 3 - j
        y = jax.lax.dot_general(
            lhs3[:n * rows], r, (((1,), (contract_rhs,)), ((), ())),
            preferred_element_type=jnp.float32)
        for i in range(n):
            part = y[i * rows:(i + 1) * rows]
            total = part if total is None else total + part
    return total


def _ragged_kernel(tables_ref, qlens_ref, lens_ref, layer_ref, *refs,
                   block_size, sm_scale, group, heads, quant, cq,
                   windowed=False, rep=1):
    """Grid (B,): one grid step a slot, and inside it a loop over the
    slot's LIVE groups of G table entries — entries below `ceil((ctx +
    q_len) / block_size)` — so that nothing is run, and nothing
    copied, for the rest of the table. The pools stay in HBM; each live
    block is one async copy into a `[2, G, bs, H * D]` buffer, and
    while one group is attended over the next live group (of this
    slot, or the first of the next slot) is in flight into the other
    buffer. A parked slot (trash block, position 0) costs one block.

    The heads stay FLAT, as the pool holds them. Row `h` of the
    block-diagonal query `[Hp, H * D]` holds head h's query in lanes
    `h * D .. (h + 1) * D` and zeros elsewhere, so ONE product with the
    `[T, H * D]` key tile gives every head's scores `[Hp, T]` (heads
    on sublanes, positions on lanes), and `p @ V` gives `[Hp, H * D]`
    whose diagonal blocks are the heads' outputs — no split of a tile
    into heads, which on the TPU is a relayout of it. Products are
    float32 (_pieces); the running max, sum and accumulator are
    float32 scratch carried across the slot's groups.

    `windowed`: a fifth prefetched scalar, the layer's window (0:
    none). Query j of the slot sees the keys `ctx + j - window <
    position <= ctx + j`, so the slot's loop STARTS at the group that
    holds `ctx - window + 1` (the first key its first query sees):
    the groups wholly before it are neither copied nor multiplied,
    and the mask cuts inside the first. With window 0 the loop starts
    at group 0.

    `rep` > 1, grouped key-value heads: the pool's row holds `heads`
    key-value heads and q comes as `[Cq * Hp, D]`, a query head a row.
    Row h of the block-diagonal query lies over the lanes of key-value
    head `h // rep`, so `rep` rows share a diagonal block; the output
    is each row's own block, `[Cq * Hp, D]`."""
    if windowed:
        win_ref, *refs = refs
    q_ref, *refs = refs
    if quant:
        k_hbm, v_hbm, ks_ref, vs_ref, *refs = refs
    else:
        k_hbm, v_hbm, *refs = refs
    (o_ref, kbuf, vbuf, sems, slot_ref, q3_ref, acc_ref, m_ref,
     l_ref) = refs
    streams = ((k_hbm, kbuf), (v_hbm, vbuf))
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    g_, bs = group, block_size
    max_blocks = tables_ref.shape[1]
    width = kbuf.shape[-1]
    d = width // heads
    rows = acc_ref.shape[0]                   # Cq * Hp
    hp = rows // cq
    t = g_ * bs
    lyr = layer_ref[0]
    win = win_ref[0] if windowed else None

    def live_blocks(bi):
        """Table entries slot `bi` attends over: up to its chunk's last
        visible key, at least one (a parked slot's trash block)."""
        n = (lens_ref[bi] + qlens_ref[bi] + bs - 1) // bs
        return jnp.clip(n, 1, max_blocks)

    def first_group(bi):
        """The first group slot `bi` attends over: 0, or under a
        window the group of the first key its first query sees."""
        if not windowed:
            return 0
        bi = jnp.minimum(bi, nb - 1)    # asked of the slot after the last
        lo = jnp.where(win > 0, jnp.maximum(lens_ref[bi] - win + 1, 0), 0)
        return lo // t

    def copies(bi, gi, buf_i, go):
        """Start (or wait for) the copies of group `gi` of slot `bi`
        into buffer `buf_i`: one per live block and pool."""
        n = live_blocks(bi)
        for g in range(g_):
            @pl.when(gi * g_ + g < n)
            def _():
                # waiting needs the copy's shape only, not its source
                blk = tables_ref[bi, gi * g_ + g] if go == "start" else 0
                for si, (hbm, buf) in enumerate(streams):
                    cp = pltpu.make_async_copy(
                        hbm.at[lyr, blk], buf.at[buf_i, g],
                        sems.at[buf_i, si])
                    cp.start() if go == "start" else cp.wait()

    @pl.when(b == 0)
    def _first():
        # lanes of a buffer no copy has reached are masked, but a
        # masked p of 0.0 times whatever fast memory held is not 0.0
        for _, buf in streams:
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        copies(0, first_group(0), 0, "start")

    if rep == 1:
        rowi = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (hp, width), 1)
        diag = (lane >= rowi * d) & (lane < (rowi + 1) * d)  # head h's

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    if rep == 1:
        q = q_ref[0].astype(jnp.float32) * sm_scale      # [Cq, H * D]
        qb = jnp.concatenate(
            [jnp.where(diag, q[j:j + 1], 0.0) for j in range(cq)], axis=0)
    else:
        # [Cq * Hp, D], a query head a row -> its key-value head's lanes
        q = q_ref[0].astype(jnp.float32) * sm_scale
        kvh = (jax.lax.broadcasted_iota(jnp.int32, (rows, d), 0) % hp) \
            // rep
        qb = jnp.concatenate([jnp.where(kvh == g, q, 0.0)
                              for g in range(heads)], axis=1)
    for i, piece in enumerate(_pieces(qb)):
        q3_ref[i * rows:(i + 1) * rows] = piece

    ctx = lens_ref[b]
    qlen = qlens_ref[b]
    n_groups = (live_blocks(b) + g_ - 1) // g_
    g0 = first_group(b)

    def tile(buf, buf_i):
        """The group's rows `[T, H * D]`, exact as bfloat16 pieces."""
        x = buf[buf_i]
        if x.dtype == jnp.bfloat16:
            return [x.reshape(t, width)]
        if quant:           # int8 / fp8 values ARE bfloat16 values
            return [x.astype(jnp.float32).astype(jnp.bfloat16)
                    .reshape(t, width)]
        return _pieces(x.reshape(t, width))

    def attend(gi, buf_i):
        more = gi + 1 < n_groups
        nbi = jnp.where(more, b, b + 1)
        ngi = jnp.where(more, gi + 1,
                        first_group(b + 1))

        @pl.when(nbi < nb)
        def _prefetch():
            copies(nbi, ngi, 1 - buf_i, "start")
        copies(b, gi, buf_i, "wait")
        s = _dot_pieces(q3_ref[...], tile(kbuf, buf_i), 1)
        if quant:       # dequant: stored * scale / GRID, a key a head
            s = s * jnp.concatenate([ks_ref[0, gi]] * cq, axis=0)
        pos = gi * t + jax.lax.broadcasted_iota(jnp.int32, (hp, t), 1)
        mask = jnp.concatenate(
            [(pos <= ctx + j) & (j < qlen) for j in range(cq)], axis=0)
        if windowed:
            mask &= jnp.concatenate(
                [(win <= 0) | (pos > ctx + j - win) for j in range(cq)],
                axis=0)
        s = jnp.where(mask, s, NEG_INF)                  # [rows, T]
        m_prev = m_ref[...]                              # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                  keepdims=True)
        m_ref[...] = m_new
        if quant:       # (a masked lane's scale may be anything)
            p = jnp.where(mask, p * jnp.concatenate([vs_ref[0, gi]] * cq,
                                                    axis=0), 0.0)
        p3 = jnp.concatenate(_pieces(p), axis=0)         # [3 rows, T]
        pv = _dot_pieces(p3, tile(vbuf, buf_i), 0)       # [rows, H*D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        return 1 - buf_i

    slot_ref[0] = jax.lax.fori_loop(g0, n_groups, attend, slot_ref[0])

    l = l_ref[...]
    o = acc_ref[...] / jnp.where(l <= 0.0, 1.0, l)       # [rows, H*D]
    if rep == 1:
        o_ref[0] = jnp.concatenate(
            [jnp.sum(jnp.where(diag, o[j * hp:(j + 1) * hp], 0.0),
                     axis=0, keepdims=True) for j in range(cq)],
            axis=0).astype(o_ref.dtype)
    else:
        out = jnp.zeros((rows, d), jnp.float32)
        for g in range(heads):
            out = out + jnp.where(kvh == g, o[:, g * d:(g + 1) * d], 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def ragged_paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                  q_lens, ctx_lens,
                                  sm_scale: Optional[float] = None,
                                  interpret: Optional[bool] = None,
                                  k_scales=None, v_scales=None,
                                  layer: Optional[int] = None,
                                  window=None):
    """The blocked ragged kernel (_ragged_kernel): a grid step a slot,
    scoring the slot's whole Cq-wide chunk against its live blocks, G
    at a time, so prefill chunks and decode singles share one
    executable shape and a slot costs what its context spans, not what
    its table could hold.

    Quantized pools (k_scales / v_scales given) run the same body: an
    int8/fp8 block is copied as it is stored, and the dequant (stored
    * scale / GRID) is folded into the scores and the probabilities
    inside the loop — the fp32 KV never exists. The `[bs, H]` absmax
    scale rows (a sixteenth of their int8 rows' bytes at D = 64) are
    gathered through the tables beforehand, heads on sublanes and
    positions on lanes, the layout the scores have: their minor axis
    of H is no copy's shape on the TPU.

    With `layer` the pools are the engine's stacked arrays, `[layers,
    N, bs, H * D]` with the heads flat (scales `[layers, N, bs, H]`),
    which is the layout the kernel works in; a single layer's `[N, bs,
    H, D]` pool (tests) is viewed as one. `layer` is a scalar-prefetch
    operand like the tables, so it may be a TRACED scalar: the looped
    family's layer loop (generation/looped.py) reads cache slot `pass
    * layers + layer` from inside a `lax.scan`. `window` (None: the
    program without one) is a fifth such scalar, so a layer loop may
    scan it beside `layer`; a pool row narrower than q's heads is
    grouped key-value heads (_ragged_kernel, `windowed` and `rep`)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _use_interpret()
    b, cq, h, d = q.shape
    quant = k_scales is not None
    if layer is None:
        n, bs, hkv = k_pool.shape[:3]
        k_pool = k_pool.reshape(1, n, bs, hkv * d)
        v_pool = v_pool.reshape(1, n, bs, hkv * d)
        if quant:
            k_scales = k_scales.reshape(1, n, bs, hkv)
            v_scales = v_scales.reshape(1, n, bs, hkv)
        layer = 0
    bs, width = k_pool.shape[2:]
    hkv = width // d
    rep = h // hkv                 # query heads a key-value head
    if quant and rep > 1:
        raise NotImplementedError(
            "a quantized pool under grouped key-value heads: no family "
            "serves one (scale rows are a key-value head's, scores a "
            "query head's)")
    m = block_tables.shape[1]
    g = blocks_per_step(bs, width * k_pool.dtype.itemsize, m)
    hp = -(-h // 16) * 16          # a bfloat16 tile's sublanes
    rows = cq * hp
    groups = -(-m // g)
    idx = lambda bi, *_: (bi, 0, 0)  # noqa: E731
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    if rep == 1:
        row_spec = pl.BlockSpec((1, cq, width), idx)
        q_in = q.reshape(b, cq, width)
    else:       # a query head a row, padded to the tile's sublanes
        row_spec = pl.BlockSpec((1, rows, d), idx)
        q_in = jnp.pad(q, ((0, 0), (0, 0), (0, hp - h), (0, 0))
                       ).reshape(b, rows, d)
    in_specs = [row_spec, hbm, hbm]
    operands = [q_in, k_pool, v_pool]
    if quant:
        inv = _inv_grid(k_pool.dtype)

        def scale_rows(sc):           # [B, groups, Hp, G * bs]
            sc = sc[layer, block_tables].reshape(b, m * bs, h) * inv
            sc = jnp.pad(jnp.transpose(sc, (0, 2, 1)),
                         ((0, 0), (0, hp - h),
                          (0, (groups * g - m) * bs)))
            return jnp.transpose(sc.reshape(b, hp, groups, g * bs),
                                 (0, 2, 1, 3))
        in_specs += [pl.BlockSpec(
            (1, groups, hp, g * bs),
            lambda bi, *_: (bi, 0, 0, 0))] * 2
        operands += [scale_rows(k_scales), scale_rows(v_scales)]
    scratch = [
        pltpu.VMEM((2, g, bs, width), k_pool.dtype),
        pltpu.VMEM((2, g, bs, width), v_pool.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((1,), jnp.int32),               # the buffer in use
        pltpu.VMEM((3 * rows, width), jnp.bfloat16),  # query pieces
        pltpu.VMEM((rows, width), jnp.float32),    # acc
        pltpu.VMEM((rows, 1), jnp.float32),        # running max
        pltpu.VMEM((rows, 1), jnp.float32),        # running denom
    ]
    kern = functools.partial(
        _ragged_kernel, block_size=bs, sm_scale=sm_scale, group=g,
        heads=hkv, quant=quant, cq=cq, windowed=window is not None,
        rep=rep)
    scalars = [block_tables.astype(jnp.int32), q_lens.astype(jnp.int32),
               ctx_lens.astype(jnp.int32),
               jnp.asarray(layer, jnp.int32).reshape(1)]
    if window is not None:
        scalars.append(jnp.asarray(window, jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_tables, q_lens, ctx_lens, layer (and the window)
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_in.shape, q.dtype),
        # the copies of a slot's first group start in the slot before
        # it: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(*scalars, *operands)
    if rep > 1:
        return out.reshape(b, cq, hp, d)[:, :, :h]
    return out.reshape(b, cq, h, d)


def paged_attention_pallas(q, k_pool, v_pool, block_tables, ctx_lens,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           k_scales=None, v_scales=None,
                           layer: Optional[int] = None, window=None):
    """Single-token decode kernel: Cq == 1 delegation to the ragged
    kernel (same visible-count ctx_lens convention as the reference
    specialization above)."""
    ctx = jnp.asarray(ctx_lens)
    out = ragged_paged_attention_pallas(
        q[:, None], k_pool, v_pool, block_tables,
        jnp.ones_like(ctx), ctx - 1, sm_scale, interpret,
        k_scales=k_scales, v_scales=v_scales, layer=layer, window=window)
    return out[:, 0]


# ---------------------------------------------------------------------------
# public entry: the form follows the backend (+ the trace-scoped pin)
# ---------------------------------------------------------------------------

# Trace-scoped kernel-form pin: the engine's `kernel=` argument, the
# dispatch policy's winning form (paddle_tpu/autotune.py) and the
# tests must be able to bake a form into a compile without a
# process-global switch (two engines in one process may resolve
# different forms). The engine wraps its trace-time construction in
# kernel_form(...) and puts the RESOLVED form into its program
# fingerprint meta (kern=...), so a cached program of one form never
# serves the other.
_FORM_OVERRIDE: Optional[str] = None


class kernel_form:
    """Context manager pinning the kernel form ("reference"|"pallas")
    for computations TRACED inside the block. None passes through to
    what the backend resolves (resolved_form)."""

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
    kernel_form pin, else the backend's — the Pallas kernel on a TPU,
    the reference form everywhere else (XLA:CPU, where the kernel
    would run interpreted, and every oracle)."""
    if _FORM_OVERRIDE is not None:
        return _FORM_OVERRIDE
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def paged_attention(q, k_pool, v_pool, block_tables, ctx_lens,
                    sm_scale: Optional[float] = None,
                    k_scales=None, v_scales=None,
                    layer: Optional[int] = None, window=None):
    """Decode-step attention over the paged KV pool, in the form
    resolved_form() answers: "pallas", the blocked kernel, on a TPU
    (interpret mode where a test pins it elsewhere); "reference", the
    parity path (the oracle's own attention core), on every other
    backend; a kernel_form block pins either.
    k_scales/v_scales
    (quantized pools, paddle_tpu/quant) flow to the dequant-fused
    forms of both paths; None = the untouched fp32 path. `layer`
    (an int or a traced scalar) says the pools are the stacked
    `[layers, N, bs, ...]` arrays and picks the layer to read, in both
    forms without a slice. Pools may be float32, bfloat16 (read as
    they are and upcast where they are multiplied) or quantized.

    `window` (an int or a traced scalar, as `layer`; 0: none) keeps a
    query at position i to the keys `i - window < j <= i`. The
    reference form masks; the Pallas form starts a slot's loop at the
    first group that holds a visible key, so a window layer costs
    what the window spans, not the context. With `window=None` both
    forms are the program they were before there was one.

    GROUPED key-value heads: where the pool's row holds fewer heads
    than q (`H * D` a multiple of the row), query head h reads
    key-value head `h // rep`."""
    mode = resolved_form()
    # ONE device-trace name for the gather and the attention over it,
    # in either form: a kernel change is read by the same metric
    with jax.named_scope("paged_attention"):
        if mode == "pallas":
            return paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                          ctx_lens, sm_scale,
                                          k_scales=k_scales,
                                          v_scales=v_scales, layer=layer,
                                          window=window)
        return paged_attention_reference(q, k_pool, v_pool, block_tables,
                                         ctx_lens, sm_scale,
                                         k_scales=k_scales,
                                         v_scales=v_scales, layer=layer,
                                         window=window)


def ragged_paged_attention(q, k_pool, v_pool, block_tables, q_lens,
                           ctx_lens, sm_scale: Optional[float] = None,
                           k_scales=None, v_scales=None,
                           layer: Optional[int] = None, window=None):
    """Mixed prefill+decode attention over the paged KV pool: q
    `[B, Cq, H, D]` with per-row true query length (1 = decode, chunk
    width = prefill). Routed like the decode entry (resolved_form:
    the backend's form, or a kernel_form pin); k_scales /
    v_scales select the quantized-KV dequant-fused forms."""
    mode = resolved_form()
    with jax.named_scope("paged_attention"):
        if mode == "pallas":
            return ragged_paged_attention_pallas(
                q, k_pool, v_pool, block_tables, q_lens, ctx_lens,
                sm_scale, k_scales=k_scales, v_scales=v_scales,
                layer=layer, window=window)
        return ragged_paged_attention_reference(
            q, k_pool, v_pool, block_tables, q_lens, ctx_lens, sm_scale,
            k_scales=k_scales, v_scales=v_scales, layer=layer,
            window=window)
