"""The LOOPED decoder family: one stack of layers run several times a
token (docs/generation.md, "Model families").

A second family beside `model.py`'s GPT block, reached by the engine
through the config object alone (`cfg.forward_full`,
`cfg.forward_paged`, `cfg.kv_layers`, `cfg.kv_row`): rotary positions,
gain-only RMSNorm in sandwich form, a SiLU-gated feed-forward, heads
whose size is not `hidden / heads`, no bias anywhere, and the loop:

    x = E[token]
    for t in range(total_ut_steps):          # a PASS
        for l in range(num_hidden_layers):
            a = Attn_l(RMS1_l(x); cache slot t * layers + l)
            x = x + RMS2_l(a)
            u = RMS3_l(x)
            x = x + RMS4_l(W_down_l(silu(W_gate_l u) * W_up_l u))
        x = RMS_f(x)                         # enters the next pass
    logits = W_head x

The weights of pass t are those of pass 0; the keys and values are
not: a token holds `total_ut_steps * num_hidden_layers` K rows and as
many V rows, and the KV pools are `[kv_layers, N, block_size, kv_heads
* head_dim]` (model.forward_paged's flat layout, PERF.md PR 28).

THE PROGRAM DOES NOT GROW WITH DEPTH. The layers' weights are stacked
`[layers, ...]` arrays and both forwards are a `lax.scan` over passes
around a `lax.scan` over layers: one loop body is traced and compiled,
whatever `num_hidden_layers` and `total_ut_steps` say (the GPT block's
12 unrolled layers compile in 68 s; 192 would not do). The pools are
the loop's carry, updated in place (`.at[slot, blk, off].set`, then
`paged_attention(..., layer=slot)` with `slot` a traced scalar), so a
caller that donates them gets its arrays back, as in the GPT family.

PRECISION. Weights and pools are served in the dtype they arrive in.
A matmul rounds its activation operand to the weight's dtype and
accumulates in float32 (`_mm`); with float32 weights that is exactly
`x @ w`. The residual stream, the norms, the rotary and the softmax
stay float32; a K or V row is rounded once, to the pool's dtype, where
it is written.

The exit gate (`gate_w`, `gate_b`: Linear(hidden, 1)) is held among
the weights and decides nothing: at the published
`early_exit_threshold` of 1 every token takes every pass. There is no
adaptive-exit path.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import quant as _quant
from ..kernels.paged_attention import attend_reference, paged_attention

__all__ = ["LoopedDecoderConfig", "init_params", "forward_full",
           "forward_paged"]

# the stacked leaves, `[layers, ...]` each: what the layer loop scans
LAYER_LEAVES = ("ln1", "wqkv", "wo", "ln2", "ln3", "w_gu", "w_down",
                "ln4")


@dataclass(frozen=True)
class LoopedDecoderConfig:
    """The published keys of a looped decoder's `config.json`, and the
    one number a deployment adds: `max_seq_len`, the CONTEXT CAP the
    engine sizes its block tables and attention lanes by. Rotary
    positions need no table, so the cap is the deployment's to choose,
    up to the model's `max_position_embeddings`."""
    vocab_size: int = 128
    hidden_size: int = 64
    num_hidden_layers: int = 3
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    head_dim: int = 16
    intermediate_size: int = 176
    total_ut_steps: int = 2
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    max_seq_len: int = 512

    # post-training weight quantization (quant.quantize_decoder_params)
    # knows the GPT family's flat leaves only
    weight_quant = False

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "grouped key-value heads are not served: %d heads, %d "
                "key-value heads" % (self.num_attention_heads,
                                     self.num_key_value_heads))
        if self.head_dim % 2:
            raise ValueError("rotary needs an even head_dim, got %d"
                             % self.head_dim)
        if not 1 <= self.max_seq_len <= self.max_position_embeddings:
            raise ValueError(
                "context cap %d outside the model's %d positions"
                % (self.max_seq_len, self.max_position_embeddings))

    @classmethod
    def from_source(cls, source: dict, max_context: int):
        """The config from a published `config.json` (as a dict; keys
        this family does not read are ignored) and the deployment's
        context cap."""
        keys = [k for k in cls.__dataclass_fields__ if k != "max_seq_len"]
        return cls(max_seq_len=int(max_context),
                   **{k: source[k] for k in keys})

    # --- the cache's geometry: what the engine sizes its pools by ------
    @property
    def kv_layers(self) -> int:
        return self.total_ut_steps * self.num_hidden_layers

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def kv_row(self) -> int:
        return self.num_key_value_heads * self.head_dim

    def cache_slot(self, t, layer):
        """The cache layer that `layer` of pass `t` writes and reads:
        every pass keeps keys and values of its own."""
        return t * self.num_hidden_layers + layer

    def meta(self) -> dict:
        """JSON-able identity for program_cache.fn_fingerprint: every
        field changes the compiled program."""
        return dict(family="looped",
                    **{k: getattr(self, k)
                       for k in self.__dataclass_fields__})

    # --- the seam the engine calls -------------------------------------
    def forward_full(self, params, tokens, lengths, attn_lanes: int = 0):
        return forward_full(self, params, tokens, lengths, attn_lanes)

    def forward_paged(self, params, k_pools, v_pools, block_tables,
                      ctx_lens, tokens, k_scale_pools=None,
                      v_scale_pools=None):
        return forward_paged(self, params, k_pools, v_pools,
                             block_tables, ctx_lens, tokens,
                             k_scale_pools, v_scale_pools)


def leaf_shapes(cfg: LoopedDecoderConfig) -> dict:
    """name -> (shape, fan_in or None for a unit gain / zero bias).
    q, k and v share one matrix, gate and up another: a layer is five
    matmuls."""
    h, v, n = cfg.hidden_size, cfg.vocab_size, cfg.num_hidden_layers
    qd = cfg.num_attention_heads * cfg.head_dim
    i = cfg.intermediate_size
    return {
        "tok_emb": ((v, h), None), "unembed": ((h, v), h),
        "norm_f": ((h,), None),
        "gate_w": ((h, 1), h), "gate_b": ((1,), None),
        "ln1": ((n, h), None), "ln2": ((n, h), None),
        "ln3": ((n, h), None), "ln4": ((n, h), None),
        "wqkv": ((n, h, qd + 2 * cfg.kv_row), h),
        "wo": ((n, qd, h), qd),
        "w_gu": ((n, h, 2 * i), h),
        "w_down": ((n, i, h), i),
    }


def init_params(cfg: LoopedDecoderConfig, seed: int = 0,
                dtype=jnp.float32) -> dict:
    """Gaussian init, numpy RNG (host-side, deterministic by seed):
    N(0, 0.02) embedding, N(0, 1/sqrt(fan_in)) matrices, unit gains."""
    rng = np.random.default_rng(seed)
    p = {}
    for name, (shape, fan_in) in leaf_shapes(cfg).items():
        if name == "tok_emb":
            w = rng.normal(0.0, 0.02, shape)
        elif name == "gate_b":
            w = np.zeros(shape)
        elif fan_in is None:
            w = np.ones(shape)
        else:
            w = rng.normal(0.0, 1.0 / math.sqrt(fan_in), shape)
        p[name] = jnp.asarray(w, dtype=dtype)
    return p


def _mm(x, w):
    """`x @ w`, the activation rounded to the weight's dtype, float32
    accumulation and result."""
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * g


def _rope_tables(cfg: LoopedDecoderConfig, positions):
    """positions `[...]` int32 -> (cos, sin) `[..., head_dim]` float32,
    the `rotate_half` form's: both halves of the head share the
    angles `pos * theta ** (-2i / head_dim)`."""
    half = cfg.head_dim // 2
    inv = 1.0 / (cfg.rope_theta ** (np.arange(half, dtype=np.float64)
                                    / half))
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv, jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x, cos, sin):
    """x `[..., heads, head_dim]`, cos/sin `[..., head_dim]`."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[..., None, :] + rot * sin[..., None, :]


def _qkv(cfg: LoopedDecoderConfig, w, x, cos, sin):
    """x `[..., h]` -> q `[..., heads, D]`, k, v `[..., kv_heads, D]`,
    q and k rotated to the token's position."""
    with jax.named_scope("qkv"):
        qkv = _mm(_rms(x, w["ln1"], cfg.rms_norm_eps), w["wqkv"])
        qd = cfg.num_attention_heads * cfg.head_dim
        lead = x.shape[:-1]
        q = qkv[..., :qd].reshape(lead + (cfg.num_attention_heads,
                                          cfg.head_dim))
        k = qkv[..., qd:qd + cfg.kv_row].reshape(
            lead + (cfg.kv_heads, cfg.head_dim))
        v = qkv[..., qd + cfg.kv_row:].reshape(
            lead + (cfg.kv_heads, cfg.head_dim))
        with jax.named_scope("rope"):
            q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    return q, k, v


def _after_attention(cfg: LoopedDecoderConfig, w, x, o):
    """The rest of a layer: o `[..., heads * D]` merged heads."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("attn_out"):
        x = x + _rms(_mm(o, w["wo"]), w["ln2"], eps)
    with jax.named_scope("mlp"):
        gu = _mm(_rms(x, w["ln3"], eps), w["w_gu"])
        i = cfg.intermediate_size
        m = _mm(jax.nn.silu(gu[..., :i]) * gu[..., i:], w["w_down"])
        x = x + _rms(m, w["ln4"], eps)
    return x


def _loop(cfg: LoopedDecoderConfig, params, layer, carry):
    """`carry = layer(carry, layer's weights, cache slot)` over every
    layer of every pass, the final norm on `carry[0]` (the residual
    stream) at the end of each pass. Returns (carry, what `layer`
    emitted, stacked `[passes, layers, ...]`)."""
    stack = {n: params[n] for n in LAYER_LEAVES}
    layers = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)

    def one_pass(carry, t):
        with jax.named_scope("loop_pass"):
            def body(c, xs):
                w, l = xs
                return layer(c, w, cfg.cache_slot(t, l))
            carry, ys = jax.lax.scan(body, carry, (stack, layers))
            x = _rms(carry[0], params["norm_f"], cfg.rms_norm_eps)
        return (x,) + tuple(carry[1:]), ys
    return jax.lax.scan(one_pass, carry,
                        jnp.arange(cfg.total_ut_steps, dtype=jnp.int32))


def forward_full(cfg: LoopedDecoderConfig, params: dict, tokens,
                 lengths, attn_lanes: int = 0):
    """Full-context forward, model.forward_full's contract: tokens
    `[B, S]`, lengths `[B]` -> (logits `[B, vocab]` at position
    lengths-1, k_cache, v_cache each `[kv_layers, B, S, kv_heads,
    head_dim]`). `attn_lanes` pads the attention's key axis to the
    paged path's lane count, so that the streams the tests compare
    agree (same reason as there; tests/test_generation_looped.py::
    test_engine_streams_equal_the_naive_generators)."""
    b, s = tokens.shape
    pos = jnp.arange(s, dtype=jnp.int32)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    cos, sin = _rope_tables(cfg, pos)                      # [S, D]
    lanes = int(attn_lanes) if attn_lanes else s
    if lanes < s:
        raise ValueError("attn_lanes %d < sequence length %d"
                         % (lanes, s))
    kpos = jnp.arange(lanes, dtype=jnp.int32)
    visible = kpos[None, :] < lengths[:, None]
    causal = pos[None, :, None] >= kpos[None, None, :]
    mask = (causal & visible[:, None, :])[:, None]         # [B,1,S,L]
    pad = ((0, 0), (0, lanes - s), (0, 0), (0, 0))
    sm_scale = 1.0 / math.sqrt(cfg.head_dim)

    def layer(carry, w, slot):
        x, = carry
        q, k, v = _qkv(cfg, w, x, cos, sin)                # [B,S,H,D]
        o = attend_reference(q.transpose(0, 2, 1, 3),
                             jnp.pad(k, pad).transpose(0, 2, 1, 3),
                             jnp.pad(v, pad).transpose(0, 2, 1, 3),
                             mask, sm_scale)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        return (_after_attention(cfg, w, x, o),), (k, v)
    (x,), (ks, vs) = _loop(cfg, params, layer, (x,))
    logits = _mm(x, params["unembed"])                     # [B, S, V]
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None].astype(jnp.int32),
        axis=1)[:, 0]
    shape = (cfg.kv_layers,) + ks.shape[2:]
    return last, ks.reshape(shape), vs.reshape(shape)


def forward_paged(cfg: LoopedDecoderConfig, params: dict, k_pools,
                  v_pools, block_tables, ctx_lens, tokens,
                  k_scale_pools=None, v_scale_pools=None):
    """The engine's mixed step, model.forward_paged's contract: tokens
    `[B]` (each slot's token at position ctx_lens), pools `[kv_layers,
    N, bs, kv_heads * head_dim]` -> (logits `[B, vocab]`, the pools
    with this step's rows written). The pools (and, quantized, their
    scale pools) are the loop's carry: layer `l` of pass `t` writes
    and reads cache slot `t * layers + l`."""
    scope = jax.named_scope
    b = tokens.shape[0]
    bs = k_pools.shape[2]
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)  # [B, h]
        cos, sin = _rope_tables(cfg, ctx_lens)             # [B, D]
    sm_scale = 1.0 / math.sqrt(cfg.head_dim)
    with scope("kv_write"):
        blk = jnp.take_along_axis(
            block_tables, (ctx_lens // bs)[:, None].astype(jnp.int32),
            axis=1)[:, 0]                                  # [B]
        off = ctx_lens % bs
    quant_kv = k_scale_pools is not None
    row = (b,) + k_pools.shape[3:]          # a slot's K or V, as stored

    def layer(carry, w, slot):
        x, kp, vp = carry[:3]
        q, k, v = _qkv(cfg, w, x, cos, sin)                # [B,H,D]
        scales = ()
        with scope("kv_write"):
            if quant_kv:
                k, ksc = _quant.quantize_kv_rows(k, kp.dtype)
                v, vsc = _quant.quantize_kv_rows(v, vp.dtype)
                scales = (carry[3].at[slot, blk, off].set(ksc),
                          carry[4].at[slot, blk, off].set(vsc))
            kp = kp.at[slot, blk, off].set(
                k.reshape(row).astype(kp.dtype))
            vp = vp.at[slot, blk, off].set(
                v.reshape(row).astype(vp.dtype))
        o = paged_attention(q, kp, vp, block_tables, ctx_lens + 1,
                            sm_scale=sm_scale,
                            k_scales=scales[0] if quant_kv else None,
                            v_scales=scales[1] if quant_kv else None,
                            layer=slot)                    # [B,H,D]
        x = _after_attention(cfg, w, x, o.reshape(b, -1))
        return (x, kp, vp) + scales, None
    carry = (x, k_pools, v_pools)
    if quant_kv:
        carry += (k_scale_pools, v_scale_pools)
    carry, _ = _loop(cfg, params, layer, carry)
    with scope("unembed"):
        logits = _mm(carry[0], params["unembed"])          # [B, V]
    return (logits,) + tuple(carry[1:])
