"""Pure-functional decoder model for the generation engine.

A small GPT-style pre-LN transformer expressed as (config, params dict,
forward functions) — no layers framework, no Program: the generation
subsystem needs a model whose full-context forward is the reference
for its paged-incremental one, so both are written here against the
same primitive ops in the same order.

The parity contract:

    forward_full(tokens[:, :t+1]) logits at position t
        ~= forward_paged(token t, pools holding positions 0..t-1)

to within rounding, not bit for bit: both paths route attention
through kernels.paged_attention.attend_reference (same einsums, same
finite NEG_INF masking — padded/masked lanes contribute exact 0.0)
over a key axis of the same width (`attn_lanes`) and run float32, but
a backend picks a matmul's tiling from the batch's shape, so the last
bits of a row move with its batch (a few ULP on XLA:CPU; logit gaps of
4.7e-3 and 0.011 between the kernel forms on the chip, PERF.md). Held
by tests/test_generation.py::test_paged_decode_bitwise_parity_every_step
and tests/test_kernels.py::
test_chunked_prefill_mixed_batch_bitwise_vs_forward_full (a tolerance
ten times the largest gap measured, and a planted fault that reads far
over it); the engine tests hold the token STREAMS to NaiveGenerator's.

Params are a flat dict of jnp arrays — pytree-friendly for jit and for
program_cache.exported_entry avals.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.paged_attention import (NEG_INF, attend_reference,
                                       paged_attention)
from .. import quant as _quant

__all__ = ["DecoderConfig", "init_params", "forward_full",
           "forward_paged"]

# every weight matmul / embedding gather routes through these seams:
# with no '<name>::scale' key in params they reduce to the EXACT
# `x @ params[name]` / `params[name][idx]` expressions (fp32 serving
# stays bitwise-identical); a quantized checkpoint (paddle_tpu/quant)
# switches them to int8 x int8 -> int32 -> scale (or fp8 upcast) and
# gather-then-dequant respectively
_mm = _quant.matmul
_emb = _quant.embed


@dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 128
    hidden: int = 64
    layers: int = 2
    heads: int = 4
    max_seq_len: int = 512
    mlp_ratio: int = 4

    @property
    def head_dim(self) -> int:
        if self.hidden % self.heads:
            raise ValueError("hidden %d not divisible by heads %d"
                             % (self.hidden, self.heads))
        return self.hidden // self.heads

    def meta(self) -> dict:
        """JSON-able identity for program_cache.fn_fingerprint: every
        field changes the compiled program."""
        return {"family": "gpt", "vocab": self.vocab_size,
                "hidden": self.hidden, "layers": self.layers,
                "heads": self.heads, "max_seq_len": self.max_seq_len,
                "mlp_ratio": self.mlp_ratio}

    # --- the seam the engine reaches a model FAMILY through ------------
    # (docs/generation.md, "Model families"): the cache's geometry, the
    # two forwards and whether quant.quantize_decoder_params knows the
    # leaves. generation/looped.py is the second family.
    weight_quant = True

    @property
    def kv_layers(self) -> int:
        """Layers of KV cache a token holds: one a layer."""
        return self.layers

    @property
    def kv_heads(self) -> int:
        return self.heads

    @property
    def kv_row(self) -> int:
        """Width of a token's K (or V) row in one cache layer."""
        return self.hidden

    def forward_full(self, params, tokens, lengths, attn_lanes: int = 0):
        return forward_full(self, params, tokens, lengths, attn_lanes)

    def forward_paged(self, params, k_pools, v_pools, block_tables,
                      ctx_lens, tokens, k_scale_pools=None,
                      v_scale_pools=None):
        return forward_paged(self, params, k_pools, v_pools,
                             block_tables, ctx_lens, tokens,
                             k_scale_pools, v_scale_pools)


def init_params(cfg: DecoderConfig, seed: int = 0) -> dict:
    """Gaussian init, numpy RNG (host-side, deterministic by seed)."""
    rng = np.random.default_rng(seed)
    h, v = cfg.hidden, cfg.vocab_size
    m = cfg.mlp_ratio * h

    def w(*shape, scale=None):
        if scale is None:
            scale = 1.0 / math.sqrt(shape[0])
        return jnp.asarray(rng.normal(0.0, scale, shape),
                           dtype=jnp.float32)

    p = {
        "tok_emb": w(v, h, scale=0.02),
        "pos_emb": w(cfg.max_seq_len, h, scale=0.02),
        "ln_f_g": jnp.ones((h,), jnp.float32),
        "ln_f_b": jnp.zeros((h,), jnp.float32),
        "unembed": w(h, v),
    }
    for i in range(cfg.layers):
        p.update({
            "l%d_ln1_g" % i: jnp.ones((h,), jnp.float32),
            "l%d_ln1_b" % i: jnp.zeros((h,), jnp.float32),
            "l%d_wqkv" % i: w(h, 3 * h),
            "l%d_wo" % i: w(h, h),
            "l%d_ln2_g" % i: jnp.ones((h,), jnp.float32),
            "l%d_ln2_b" % i: jnp.zeros((h,), jnp.float32),
            "l%d_w1" % i: w(h, m),
            "l%d_b1" % i: jnp.zeros((m,), jnp.float32),
            "l%d_w2" % i: w(m, h),
            "l%d_b2" % i: jnp.zeros((h,), jnp.float32),
        })
    return p


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


def _qkv(cfg: DecoderConfig, params: dict, i: int, x):
    """x [..., h] -> q, k, v each [..., heads, head_dim]."""
    qkv = _mm(params, "l%d_wqkv" % i, x)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shp = x.shape[:-1] + (cfg.heads, cfg.head_dim)
    return q.reshape(shp), k.reshape(shp), v.reshape(shp)


def _mlp(params: dict, i: int, x):
    h = jax.nn.gelu(_mm(params, "l%d_w1" % i, x) + params["l%d_b1" % i],
                    approximate=False)
    return _mm(params, "l%d_w2" % i, h) + params["l%d_b2" % i]


def forward_full(cfg: DecoderConfig, params: dict, tokens, lengths,
                 attn_lanes: int = 0):
    """Full-context forward: tokens `[B, S]` int32, lengths `[B]`
    (visible prefix per row; padding beyond it is masked out of
    attention). Returns (logits `[B, vocab]` at position lengths-1,
    k_cache, v_cache each `[layers, B, S, heads, head_dim]`) — the
    caches let a test fill a block pool with a prompt's rows.

    `attn_lanes` (static) pads the attention K/V axis to a FIXED lane
    count: the oracle's key axis as wide as the paged view. XLA
    regroups a reduction when its length changes (Tk=16 vs Tk=32 sums
    associate nonzero elements differently), so with the SAME number
    of lanes the two paths' softmax sums agree and the token streams
    the tests compare (module docstring) do not part at a near-tie.
    NaiveGenerator passes the engine's pool-table span
    (max_blocks_per_seq * block_size); 0 keeps the raw S lanes
    (standalone use).
    """
    b, s = tokens.shape
    pos = jnp.arange(s, dtype=jnp.int32)
    x = _emb(params, "tok_emb", tokens) + _emb(params, "pos_emb",
                                               pos)[None]
    lanes = int(attn_lanes) if attn_lanes else s
    if lanes < s:
        raise ValueError("attn_lanes %d < sequence length %d"
                         % (lanes, s))
    kpos = jnp.arange(lanes, dtype=jnp.int32)
    # causal AND within the visible prefix (padding lanes always off)
    visible = kpos[None, :] < lengths[:, None]             # [B, L]
    causal = pos[None, :, None] >= kpos[None, None, :]     # [1, S, L]
    mask = (causal & visible[:, None, :])[:, None]         # [B,1,S,L]
    pad = ((0, 0), (0, lanes - s), (0, 0), (0, 0))
    sm_scale = 1.0 / math.sqrt(cfg.head_dim)
    ks, vs = [], []
    for i in range(cfg.layers):
        xn = _ln(x, params["l%d_ln1_g" % i], params["l%d_ln1_b" % i])
        q, k, v = _qkv(cfg, params, i, xn)                 # [B,S,H,D]
        ks.append(k)
        vs.append(v)
        o = attend_reference(q.transpose(0, 2, 1, 3),
                             jnp.pad(k, pad).transpose(0, 2, 1, 3),
                             jnp.pad(v, pad).transpose(0, 2, 1, 3),
                             mask, sm_scale)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.hidden)
        x = x + _mm(params, "l%d_wo" % i, o)
        x = x + _mlp(params, i, _ln(x, params["l%d_ln2_g" % i],
                                    params["l%d_ln2_b" % i]))
    x = _ln(x, params["ln_f_g"], params["ln_f_b"])
    logits = _mm(params, "unembed", x)                     # [B, S, V]
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None].astype(jnp.int32),
        axis=1)[:, 0]
    return last, jnp.stack(ks), jnp.stack(vs)


def forward_paged(cfg: DecoderConfig, params: dict, k_pools, v_pools,
                  block_tables, ctx_lens, tokens,
                  k_scale_pools=None, v_scale_pools=None):
    """One-token-per-slot paged step: tokens `[B]` (each slot's token
    at position ctx_lens), pools `[layers, N, bs, H * D]` (the heads'
    axes flat: a token row is one contiguous line; the `reference`
    form reads a `[layers, N, bs, H, D]` pool the same), block_tables
    `[B, M]`, ctx_lens `[B]` int32 (tokens already in the cache).
    Writes each layer's new K/V into the pool at the flat slot
    `table[ctx // bs] * bs + ctx % bs`, attends over ctx+1 positions,
    returns (logits `[B, vocab]`, k_pools', v_pools').

    The pools are STATE UPDATED IN PLACE: layer i's rows go into the
    stacked array it was given with one scatter (`.at[i, blk, off]`),
    the updated array is threaded to the next layer, and attention
    reads layer i of it through `paged_attention(..., layer=i)` — no
    layer's pool is ever sliced out, and nothing is stacked at the
    end. A caller that jits this with the pools DONATED (the engine's
    `mixed` and `draft_mixed` programs) gets the arrays it
    passed back, the step's rows written; a caller that does not
    donate pays one copy of each pool at the program's edge, and
    computes the same values.

    This is the engine's MIXED step, not just decode.  A batch row is a
    *slot*: either a decode lane's next token or one prompt token of a
    prefill chunk.  Chunk-mates of the same sequence occupy adjacent
    slots with duplicated table rows and consecutive positions; because
    every layer scatters all slots' K/V before the attention gather,
    later chunk-mates see earlier ones' keys within the same call, so a
    prompt streamed through this step reads what `forward_full` reads
    at every position, to within rounding (tests/test_kernels.py).

    Inactive slots (the scheduler parks them) carry ctx_lens whose
    block-table slot is the trash block — their writes land in trash
    and their logits are garbage the scheduler never samples from.

    QUANTIZED KV (ISSUE 15): with `k_scale_pools`/`v_scale_pools`
    given (`[layers, N, bs, H]` fp32 absmax), the pools store int8/fp8:
    each slot's fresh K/V rows quantize per-token-per-head
    (quant.quantize_kv_rows) before the scatter, the scale rows scatter
    alongside, and attention dequantizes inside the kernel. Returns a
    5-tuple (logits, k_pools', v_pools', k_scale_pools',
    v_scale_pools'); the fp32 call keeps the 3-tuple and the exact
    pre-quant expressions.
    """
    # device-trace names of the step's phases (telemetry.py's
    # convention): jax.named_scope is metadata of the compiled program
    scope = jax.named_scope
    b = tokens.shape[0]
    bs = k_pools.shape[2]
    with scope("embed"):
        x = _emb(params, "tok_emb", tokens) \
            + _emb(params, "pos_emb", ctx_lens)            # [B,h]
    sm_scale = 1.0 / math.sqrt(cfg.head_dim)
    with scope("kv_write"):
        blk = jnp.take_along_axis(
            block_tables, (ctx_lens // bs)[:, None].astype(jnp.int32),
            axis=1)[:, 0]                                  # [B]
        off = ctx_lens % bs
    quant_kv = k_scale_pools is not None
    row = (b,) + k_pools.shape[3:]          # a slot's K or V, as stored
    for i in range(cfg.layers):
        with scope("qkv"):
            xn = _ln(x, params["l%d_ln1_g" % i], params["l%d_ln1_b" % i])
            q, k, v = _qkv(cfg, params, i, xn)             # [B,H,D]
        with scope("kv_write"):
            if quant_kv:
                k, ksc = _quant.quantize_kv_rows(k, k_pools.dtype)
                v, vsc = _quant.quantize_kv_rows(v, v_pools.dtype)
                k_scale_pools = k_scale_pools.at[i, blk, off].set(ksc)
                v_scale_pools = v_scale_pools.at[i, blk, off].set(vsc)
            # (a bfloat16 pool rounds the row here, once; float32 and
            # quantized rows are already the pool's type)
            k_pools = k_pools.at[i, blk, off].set(
                k.reshape(row).astype(k_pools.dtype))
            v_pools = v_pools.at[i, blk, off].set(
                v.reshape(row).astype(v_pools.dtype))
        # the kernel opens `paged_attention` itself, in either form
        o = paged_attention(q, k_pools, v_pools, block_tables,
                            ctx_lens + 1, sm_scale=sm_scale,
                            k_scales=k_scale_pools,
                            v_scales=v_scale_pools, layer=i)  # [B,H,D]
        with scope("attn_out"):
            x = x + _mm(params, "l%d_wo" % i, o.reshape(b, cfg.hidden))
        with scope("mlp"):
            x = x + _mlp(params, i, _ln(x, params["l%d_ln2_g" % i],
                                        params["l%d_ln2_b" % i]))
    with scope("unembed"):
        x = _ln(x, params["ln_f_g"], params["ln_f_b"])
        logits = _mm(params, "unembed", x)                 # [B, V]
    if quant_kv:
        return logits, k_pools, v_pools, k_scale_pools, v_scale_pools
    return logits, k_pools, v_pools
