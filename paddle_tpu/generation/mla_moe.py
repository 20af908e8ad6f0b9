"""The LATENT-attention expert family: multi-head latent attention
(MLA) over a paged pool of latent rows, routed experts with a shared
expert, leading dense layers (docs/generation.md, "Model families").

A fourth family behind the engine's config seam, imported where such
a model is built and not by `import paddle_tpu.generation`. Layer `l`
(pre-norm):

    h = x + Attn(RMS_in(x));   x = h + FFN(RMS_post(h))
    Attn: c_q = RMS_qa(W_qa x)                               [q_lora_rank]
          q = W_qb c_q -> heads x (q_nope | q_rope);  q_rope = YaRN(q_rope)
          W_kva x -> (c | k_rope);  c = RMS_kva(c);  k_rope = YaRN(k_rope)
          the cache row [c | k_rope]: ONE a position, every head's
          published: (k_nope_h | v_h) = W_kvb,h c;
              s_h,j = (q_nope_h . k_nope_h,j + q_rope_h . k_rope_j) * scale
          absorbed (the engine's step): q_lat_h = W_uk,h^T q_nope_h;
              s_h,j = (q_lat_h . c_j + q_rope_h . k_rope_j) * scale;
              o_h = W_uv,h (sum_j p_h,j c_j)
          out = W_o [o_1 .. o_heads]
    scale = (qk_nope + qk_rope)^-1/2 * m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    FFN, l < dense_layers: W_down(silu(W_gate x) * W_up x)
    FFN, sparse: moe_window.moe, the same routing and the same share
            of held experts (sigmoid scores, a bias for the choice only,
            weights over all chosen, the held experts' grouped products
            and the shared expert)
    logits = W_head RMS_f(x)

YaRN (DeepSeek-V3's published rotary, which this config's model type
runs): over the rotary part's pairs, inv_freq_i = interp_i (1 - g_i) +
extrap_i g_i, extrap_i = theta^(-2i/d), interp_i = extrap_i / factor,
g = 1 - the linear ramp between the correction dims of beta_fast and
beta_slow at the original context; the pairs are INTERLEAVED, (2i,
2i + 1), brought apart before `rotate_half` as that code does; cos and
sin scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim).

THE CACHE. A position holds one row of `kv_lora_rank + qk_rope_head_dim`
values, zeros after them up to a whole number of 128-lane tiles
(`kv_row`, kernels/latent_attention.py): the values are the row's
first `kv_lora_rank` columns, so the family declares ONE pool
(`kv_pools`) and the engine holds no value pool.

THE PROGRAM DOES NOT GROW WITH DEPTH: `moe_window._stacks`, the dense
layers one `lax.scan` and the sparse ones another, the experts' leaves
taken whole.

PRECISION as in `moe_window.py`: weights and pool in the dtype they
arrive in, a matmul rounds its activation operand to the weight's
dtype and accumulates in float32; the residual stream, the norms, the
rotary, the softmax and the router's scores are float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import latent_attention as _la
from ..kernels.paged_attention import attend_reference
from ..monitor import stat_add
from . import moe_window as _mw
from .looped import _mm, _rms

__all__ = ["LatentDecoderConfig", "init_params", "forward_full",
           "forward_paged"]

ATTN_LEAVES = ("ln_attn", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
               "wkv_b", "wo", "ln_ffn")
DENSE_LEAVES = ATTN_LEAVES + ("w_gu", "w_down")
SPARSE_LEAVES = ATTN_LEAVES + ("router", "router_bias", "s_gu", "s_down")
_LANES = 128


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class LatentDecoderConfig:
    """The published keys of the source's `config.json` (the YaRN
    group's under `rope_*`), and what a deployment adds: the experts
    this chip holds and `max_seq_len`, the context cap."""
    vocab_size: int = 128
    hidden_size: int = 64
    num_hidden_layers: int = 3
    num_attention_heads: int = 4
    q_lora_rank: int = 24
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 8
    qk_rope_head_dim: int = 8
    v_head_dim: int = 8
    intermediate_size: int = 96
    moe_intermediate_size: int = 16
    num_experts: int = 16               # the router's outputs: ALL experts
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.827
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    dense_layers: int = 1
    experts_first: int = 0
    experts_held: int = 16
    max_position_embeddings: int = 262144
    max_seq_len: int = 512

    weight_quant = False
    # the engine's pools: ONE, of latent rows (engine._pool_specs)
    kv_pools = ("latent_pools",)

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary needs an even qk_rope_head_dim, got %d"
                             % self.qk_rope_head_dim)
        if not 0 <= self.dense_layers <= self.num_hidden_layers:
            raise ValueError("%d dense layers of %d" % (
                self.dense_layers, self.num_hidden_layers))
        if not (0 <= self.experts_first and 1 <= self.experts_held
                and self.experts_first + self.experts_held
                <= self.num_experts):
            raise ValueError("experts %d..%d of %d" % (
                self.experts_first,
                self.experts_first + self.experts_held - 1,
                self.num_experts))
        if not 1 <= self.max_seq_len <= self.max_position_embeddings:
            raise ValueError(
                "context cap %d outside the model's %d positions"
                % (self.max_seq_len, self.max_position_embeddings))

    @classmethod
    def from_source(cls, source: dict, max_context: int,
                    experts_held=None):
        """The config from a published `config.json` as a dict (keys
        this family does not read are ignored), the deployment's
        context cap and the experts held here, `(first, count)`. A file
        cut to a chip's share gives the experts it HOLDS under
        `n_routed_experts` and the router's width under
        `n_routed_experts_published`."""
        def refuse(what):
            raise ValueError("this family does not serve %s" % what)
        for key, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                          ("topk_group", 1), ("topk_method", "noaux_tc"),
                          ("hidden_act", "silu"), ("moe_layer_freq", 1)):
            if source.get(key, want) != want:
                refuse("%s %r" % (key, source[key]))
        if source.get("num_nextn_predict_layers", 0):
            refuse("multi-token prediction layers (num_nextn_predict_"
                   "layers %r)" % source["num_nextn_predict_layers"])
        if not source.get("q_lora_rank"):
            refuse("a query without its low-rank projection (q_lora_rank "
                   "%r)" % source.get("q_lora_rank"))
        rope = source["rope_scaling"]
        if rope.get("type", rope.get("rope_type")) != "yarn":
            refuse("rope_scaling %r" % rope)
        total = source.get("n_routed_experts_published",
                           source["n_routed_experts"])
        first, held = experts_held or (0, source["n_routed_experts"])
        same = ("vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "n_shared_experts",
                "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
                "rope_theta", "max_position_embeddings")
        return cls(num_experts=int(total), experts_first=int(first),
                   experts_held=int(held),
                   dense_layers=int(source["first_k_dense_replace"]),
                   rope_factor=float(rope["factor"]),
                   rope_original_max_position=int(
                       rope["original_max_position_embeddings"]),
                   rope_beta_fast=float(rope["beta_fast"]),
                   rope_beta_slow=float(rope["beta_slow"]),
                   rope_mscale=float(rope["mscale"]),
                   rope_mscale_all_dim=float(rope["mscale_all_dim"]),
                   max_seq_len=int(max_context),
                   **{k: source[k] for k in same})

    # --- the cache's geometry: what the engine sizes its pool by -------
    @property
    def kv_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def kv_row(self) -> int:
        """The latent row, `[c | k_rope]`, padded to whole lane tiles."""
        n = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-n // _LANES) * _LANES

    @property
    def kv_windows(self):
        """Every layer attends the whole context: the attended counters
        sum over the layers."""
        return (0,) * self.num_hidden_layers

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - self.dense_layers

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m

    def meta(self) -> dict:
        """JSON-able identity for program_cache.fn_fingerprint: every
        field changes the compiled program."""
        return dict(family="mla_moe",
                    **{k: getattr(self, k)
                       for k in self.__dataclass_fields__})

    # --- what a step reports beside its tokens ---------------------------
    @property
    def step_stats_len(self) -> int:
        """int32 numbers `forward_paged(..., live=...)` returns last: the
        held experts' loads as `moe_window`'s, then the latent kernel's
        tiles of live slots and the live slots in a tile of more than
        one."""
        return self.sparse_layers * self.experts_held + 2

    def record_step_stats(self, stats) -> None:
        """The host's half: the routing counts as the expert family
        records them, and the kernel's tiles (docs/observability.md)."""
        _mw.ExpertDecoderConfig.record_step_stats(self, stats[:-2])
        stat_add("STAT_generation_latent_tiles", int(stats[-2]))
        stat_add("STAT_generation_latent_shared_slots", int(stats[-1]))

    # --- the seam the engine calls -------------------------------------
    def forward_full(self, params, tokens, lengths, attn_lanes: int = 0):
        return forward_full(self, params, tokens, lengths, attn_lanes)

    def forward_paged(self, params, latent_pools, block_tables, ctx_lens,
                      tokens, live=None):
        # no scale pools: the engine refuses a quantized pool for a family
        # that declares other pools than K and V
        return forward_paged(self, params, latent_pools, block_tables,
                             ctx_lens, tokens, live)


def leaf_shapes(cfg: LatentDecoderConfig) -> dict:
    """name -> (shape, fan_in | None for a unit gain | "bias"). Gate and
    up share one matrix; the experts' are stacked `[layers, experts
    held, ...]` (moe_window's names: its `moe` reads them)."""
    h, v, n_h = cfg.hidden_size, cfg.vocab_size, cfg.num_attention_heads
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    m = cfg.moe_intermediate_size
    ms = cfg.n_shared_experts * m
    e = cfg.experts_held

    def attn(n):
        return {"ln_attn": ((n, h), None), "wq_a": ((n, h, ql), h),
                "q_a_norm": ((n, ql), None),
                "wq_b": ((n, ql, n_h * (dn + dr)), ql),
                "wkv_a": ((n, h, kvl + dr), h),
                "kv_a_norm": ((n, kvl), None),
                "wkv_b": ((n, kvl, n_h * (dn + dv)), kvl),
                "wo": ((n, n_h * dv, h), n_h * dv),
                "ln_ffn": ((n, h), None)}
    out = {"tok_emb": ((v, h), None), "unembed": ((h, v), h),
           "norm_f": ((h,), None)}
    nd, ns = cfg.dense_layers, cfg.sparse_layers
    if nd:
        i = cfg.intermediate_size
        dense = dict(attn(nd), w_gu=((nd, h, 2 * i), h),
                     w_down=((nd, i, h), i))
        out.update({_mw.DENSE_PREFIX + k: s for k, s in dense.items()})
    if ns:
        out.update(attn(ns))
        out.update({
            "router": ((ns, h, cfg.num_experts), h),
            "router_bias": ((ns, cfg.num_experts), "bias"),
            "e_gu": ((ns, e, h, 2 * m), h), "e_down": ((ns, e, m, h), m),
            "s_gu": ((ns, h, 2 * ms), h), "s_down": ((ns, ms, h), ms)})
    return out


def init_params(cfg: LatentDecoderConfig, seed: int = 0,
                dtype=jnp.float32) -> dict:
    """moe_window.init_params' draw over this family's leaves."""
    return _mw.draw_params(leaf_shapes(cfg), seed, dtype)


def yarn_inv_freq(cfg: LatentDecoderConfig) -> np.ndarray:
    """`[qk_rope_head_dim / 2]` float64: the YaRN blend of the
    interpolated and the extrapolated frequencies."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extrap = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    interp = extrap / cfg.rope_factor

    def dim_of(rotations):
        return d * math.log(cfg.rope_original_max_position
                            / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    lo = max(math.floor(dim_of(cfg.rope_beta_fast)), 0)
    hi = min(math.ceil(dim_of(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - lo)
                   / ((hi + 0.001 if hi == lo else hi) - lo), 0.0, 1.0)
    keep = 1.0 - ramp           # the share of the extrapolated frequency
    return interp * (1.0 - keep) + extrap * keep


def _rope_tables(cfg: LatentDecoderConfig, positions):
    """positions `[...]` int32 -> (cos, sin) `[..., qk_rope_head_dim]`
    float32, both halves at the pairs' angles, times YaRN's scale of
    the rotary part."""
    scale = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) \
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rope(x, cos, sin):
    """x `[..., heads, d]` in the published INTERLEAVED pairs: the even
    lanes brought before the odd ones, then `rotate_half`. cos/sin
    `[..., d]` (broadcast over the heads' axis)."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos[..., None, :] + rot * sin[..., None, :]


def _query(cfg: LatentDecoderConfig, w, x, cos, sin):
    """x `[..., h]` (normed) -> q_nope `[..., heads, dn]`, q_rope
    `[..., heads, dr]` rotated."""
    with jax.named_scope("latent_q"):
        c = _rms(_mm(x, w["wq_a"]), w["q_a_norm"], cfg.rms_norm_eps)
        q = _mm(c, w["wq_b"]).reshape(
            x.shape[:-1] + (cfg.num_attention_heads, -1))
        dn = cfg.qk_nope_head_dim
        return q[..., :dn], _rope(q[..., dn:], cos, sin)


def _latent_row(cfg: LatentDecoderConfig, w, x, cos, sin):
    """x `[..., h]` (normed) -> the cache row `[..., kv_row]`:
    [RMS_kva(c) | k_rope rotated | zeros]."""
    with jax.named_scope("latent_kv"):
        a = _mm(x, w["wkv_a"])
        kvl = cfg.kv_lora_rank
        c = _rms(a[..., :kvl], w["kv_a_norm"], cfg.rms_norm_eps)
        k_rope = _rope(a[..., None, kvl:], cos, sin)[..., 0, :]
        pad = cfg.kv_row - kvl - cfg.qk_rope_head_dim
        return jnp.concatenate(
            [c, k_rope, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)


def _up(cfg: LatentDecoderConfig, w):
    """W_kvb as `[kv_lora_rank, heads, nope + v]`: (W_uk, W_uv)."""
    wkv_b = w["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                               -1)
    dn = cfg.qk_nope_head_dim
    return wkv_b[..., :dn], wkv_b[..., dn:]


def _ffn(cfg: LatentDecoderConfig, w, x, live):
    """The rest of a layer: x + FFN(RMS_post(x)), and the held experts'
    load (None in a dense layer)."""
    m, load = _mw._ffn(cfg, w, _rms(x, w["ln_ffn"], cfg.rms_norm_eps), live)
    return x + m, load


def _stacks(cfg, params, layer, carry):
    return _mw._stacks(cfg, params, layer, carry,
                       leaves=(DENSE_LEAVES, SPARSE_LEAVES))


def forward_full(cfg: LatentDecoderConfig, params: dict, tokens, lengths,
                 attn_lanes: int = 0):
    """Full-context forward in the PUBLISHED form (W_kvb decompresses
    every head's keys and values), model.forward_full's contract:
    tokens `[B, S]`, lengths `[B]` -> (logits `[B, vocab]` at position
    lengths-1, the cache rows `[kv_layers, B, S, kv_row]`, the values'
    part of them). `attn_lanes` pads the key axis to the paged path's
    lane count (looped.forward_full)."""
    b, s = tokens.shape
    pos = jnp.arange(s, dtype=jnp.int32)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    cos, sin = _rope_tables(cfg, pos)                      # [S, dr]
    lanes = int(attn_lanes) if attn_lanes else s
    if lanes < s:
        raise ValueError("attn_lanes %d < sequence length %d"
                         % (lanes, s))
    kpos = jnp.arange(lanes, dtype=jnp.int32)
    visible = kpos[None, :] < lengths[:, None]
    mask = (pos[None, :, None] >= kpos[None, None, :]) & visible[:, None]
    live = pos[None, :] < lengths[:, None]
    n_h = cfg.num_attention_heads
    pad = ((0, 0), (0, lanes - s), (0, 0), (0, 0))

    def layer(carry, w, slot, window):
        x, = carry
        hn = _rms(x, w["ln_attn"], cfg.rms_norm_eps)
        q_nope, q_rope = _query(cfg, w, hn, cos, sin)      # [B,S,H,.]
        row = _latent_row(cfg, w, hn, cos, sin)            # [B,S,R]
        kvl, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        kv = _mm(row[..., :kvl], w["wkv_b"]).reshape(b, s, n_h, -1)
        k_rope = row[..., None, kvl:kvl + cfg.qk_rope_head_dim]
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, n_h,
                                                      k_rope.shape[-1]))],
            axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)

        def keys(t):                                       # [B,H,L,D]
            return jnp.pad(t, pad).transpose(0, 2, 1, 3)
        o = attend_reference(q.transpose(0, 2, 1, 3), keys(k),
                             keys(kv[..., dn:]), mask[:, None],
                             cfg.softmax_scale)
        x = x + _mm(o.transpose(0, 2, 1, 3).reshape(b, s, -1), w["wo"])
        x, _ = _ffn(cfg, w, x, live)
        return (x,), row
    (x,), emitted = _stacks(cfg, params, layer, (x,))
    rows = jnp.concatenate(emitted, axis=0)
    x = _rms(x, params["norm_f"], cfg.rms_norm_eps)
    logits = _mm(x, params["unembed"])                     # [B, S, V]
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None].astype(jnp.int32),
        axis=1)[:, 0]
    return last, rows, rows[..., :cfg.kv_lora_rank]


def _tile_stats(cfg: LatentDecoderConfig, pool, block_tables, visible,
                live):
    """`[2]` int32: the latent kernel's tiles whose slots carry a token,
    and the live slots attended in a tile of more than one, by the
    kernel's own rule (`latent_tiles` at the Q its geometry gives)."""
    b, m = block_tables.shape
    tile = _la.slots_per_tile(cfg.num_attention_heads, pool.shape[2],
                              pool.shape[-1] * pool.dtype.itemsize, m,
                              cfg.kv_lora_rank, b)
    first, count = _la.latent_tiles(block_tables, visible, tile)
    lead = live[jnp.minimum(first, b - 1)] & (count > 0)
    return jnp.stack([jnp.sum(lead, dtype=jnp.int32),
                      jnp.sum(jnp.where(lead & (count > 1), count, 0),
                              dtype=jnp.int32)])


def forward_paged(cfg: LatentDecoderConfig, params: dict, latent_pools,
                  block_tables, ctx_lens, tokens, live=None):
    """The engine's mixed step in the ABSORBED form: tokens `[B]` (each
    slot's token at position ctx_lens), the pool `[kv_layers, N, bs,
    kv_row]` -> (logits `[B, vocab]`, the pool with this step's rows
    written) and, where `live` `[B]` bool says which slots carry a
    token, last `step_stats_len` int32: the held experts' loads
    `[sparse layers, experts_held]` over those slots, flat, then
    `_tile_stats`."""
    scope = jax.named_scope
    b = tokens.shape[0]
    bs = latent_pools.shape[2]
    count = live is not None
    if live is None:
        live = jnp.ones((b,), bool)
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)  # [B, h]
        cos, sin = _rope_tables(cfg, ctx_lens)             # [B, dr]
    with scope("kv_write"):
        blk = jnp.take_along_axis(
            block_tables, (ctx_lens // bs)[:, None].astype(jnp.int32),
            axis=1)[:, 0]                                  # [B]
        off = ctx_lens % bs
    kvl = cfg.kv_lora_rank

    def layer(carry, w, slot, window):
        x, pool = carry
        hn = _rms(x, w["ln_attn"], cfg.rms_norm_eps)
        q_nope, q_rope = _query(cfg, w, hn, cos, sin)      # [B, H, .]
        row = _latent_row(cfg, w, hn, cos, sin)            # [B, R]
        with scope("kv_write"):
            pool = pool.at[slot, blk, off].set(row.astype(pool.dtype))
        w_uk, w_uv = _up(cfg, w)
        with scope("latent_absorb"):
            q_lat = jnp.einsum("bhd,chd->bhc", q_nope.astype(w_uk.dtype),
                               w_uk, preferred_element_type=jnp.float32)
            q = jnp.concatenate(
                [q_lat, q_rope, jnp.zeros(q_rope.shape[:-1] + (
                    cfg.kv_row - kvl - q_rope.shape[-1],), jnp.float32)],
                axis=-1)                                   # [B, H, R]
        ctx = _la.latent_attention(q, pool, block_tables, ctx_lens + 1,
                                   sm_scale=cfg.softmax_scale, layer=slot,
                                   value_width=kvl)        # [B, H, kvl]
        with scope("latent_out"):
            o = jnp.einsum("bhc,chd->bhd", ctx.astype(w_uv.dtype), w_uv,
                           preferred_element_type=jnp.float32)
        with scope("attn_out"):
            x = x + _mm(o.reshape(b, -1), w["wo"])
        x, load = _ffn(cfg, w, x, live)
        return (x, pool), load
    (x, pool), emitted = _stacks(cfg, params, layer, (x, latent_pools))
    with scope("unembed"):
        x = _rms(x, params["norm_f"], cfg.rms_norm_eps)
        logits = _mm(x, params["unembed"])                 # [B, V]
    if not count:
        return logits, pool
    loads = emitted[-1] if cfg.sparse_layers else \
        jnp.zeros((0, cfg.experts_held), jnp.int32)
    return logits, pool, jnp.concatenate([
        loads.reshape(-1),
        _tile_stats(cfg, latent_pools, block_tables, ctx_lens + 1, live)])
