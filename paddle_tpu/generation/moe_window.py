"""The EXPERT decoder family: routed experts, window and full attention
layers in one stack, grouped key-value heads (docs/generation.md,
"Model families").

A third family behind the seam the engine reaches a model through
(`cfg.forward_full`, `cfg.forward_paged`, `cfg.kv_layers`, `cfg.kv_row`,
`cfg.kv_heads`, `cfg.meta()`), imported where such a model is built and
not by `import paddle_tpu.generation`. Layer `l` of the stack:

    a = Attn_l(x);   x = x + RMS_a,l(a)       (the norm on the branch's OUTPUT)
    m = FFN_l(x);    x = x + RMS_f,l(m)
    Attn_l: q = W_q x (heads of D), k = W_k x, v = W_v x (kv_heads of D);
            q and k normed per head (a gain of D); rotary (rotate_half,
            the whole head) on q and k in a WINDOW layer, none in a full
            layer; query head h reads key-value head h // rep; key j is
            visible to query i iff j <= i, and in a window layer also
            i - j < window; W_o on the merged heads
    FFN_l, l < dense_layers: W_down(silu(W_gate x) * W_up x)
    FFN_l, sparse: s = sigmoid(W_r x) in float32, num_experts scores;
            T = the num_experts_per_tok largest of s + b (b: a bias for
            the CHOICE only); w_e = routed_scaling_factor * s_e /
            sum_{j in T} s_j;  m = sum_{e in T, e HELD} w_e E_e(x) + S(x)
    logits = W_head RMS_f(x)

THE CHIP'S SHARE. The config says which experts this program holds
(`experts_first`, `experts_held` of the router's `num_experts`). The
router scores all of them, chooses among all and normalises over all
chosen; the layer adds the products of the held experts alone. What
the absent experts would add is left out, and that partial sum goes
on: there is no exchange and nothing stands in for one.

NO TOKEN IS DROPPED and the shape is fixed. A step's `slots x
experts_per_tok` (token, expert) pairs are sorted by expert, the pairs
of absent experts and of idle slots last, and the held experts'
products are ONE grouped matmul over the sorted rows
(`jax.lax.ragged_dot`, group e the rows of expert e): every routed
pair is computed whatever the imbalance, there is no capacity and no
`[tokens, experts, capacity]` tensor. `parallel/moe.py` is training's.

THE PROGRAM DOES NOT GROW WITH DEPTH: the leading dense layers are one
`lax.scan`, the sparse layers another, over stacked `[layers, ...]`
leaves; the layer's cache slot and its WINDOW (0: a full layer) are
scanned values the one compiled body reads, and the kernel takes both
as traced scalars (kernels/paged_attention.py, `layer`, `window`).

One pool geometry and one block table serve every layer: a window
layer's blocks older than its window stay in the pool and are never
read (ROADMAP R2 frees them).

PRECISION as in `looped.py`: weights and pools in the dtype they
arrive in, a matmul rounds its activation operand to the weight's
dtype and accumulates in float32; the residual stream, the norms, the
rotary, the softmax AND the router's scores are float32 (the router's
product at precision `highest`: a score decides which experts run).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.paged_attention import attend_reference, paged_attention
from ..monitor import stat_add
from .looped import _mm, _rms, _rope, _rope_tables

__all__ = ["ExpertDecoderConfig", "init_params", "forward_full",
           "forward_paged"]

# the stacked leaves a layer of either kind has, `[layers, ...]` each
ATTN_LEAVES = ("wqkv", "q_norm", "k_norm", "wo", "ln_attn", "ln_ffn")
DENSE_LEAVES = ATTN_LEAVES + ("w_gu", "w_down")
SPARSE_LEAVES = ATTN_LEAVES + ("router", "router_bias", "s_gu", "s_down")
# the routed experts' leaves, `[sparse layers, experts held, ...]`: NOT
# scanned. The grouped product is a kernel call of its own, and a
# layer's slice of a scanned leaf would be copied out for it (805 MB
# and 403 MB a layer at the published widths, twice the experts' own
# stream; seen in the step compiled for a v5e). It takes the whole
# leaf as `[layers x experts, ...]` groups instead, all but this
# layer's empty: the same trick as `paged_attention(layer=...)`.
EXPERT_LEAVES = ("e_gu", "e_down")
DENSE_PREFIX = "d_"     # the leading dense layers' leaves: `d_wqkv`, ...


@dataclass(frozen=True)
class ExpertDecoderConfig:
    """The published keys of the source's `config.json`, and what a
    deployment adds: the experts this chip holds and `max_seq_len`,
    the context cap the engine sizes its block tables by."""
    vocab_size: int = 128
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 16
    intermediate_size: int = 192
    moe_intermediate_size: int = 32
    num_experts: int = 16               # the router's outputs: ALL experts
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    # a window a layer, 0 for a full-attention layer
    sliding_windows: Tuple[int, ...] = (8, 8, 0, 8)
    dense_layers: int = 1               # leading layers with a dense FFN
    experts_first: int = 0              # the experts held here:
    experts_held: int = 16              # first .. first + held - 1
    max_position_embeddings: int = 262144
    max_seq_len: int = 512

    weight_quant = False

    def __post_init__(self):
        if len(self.sliding_windows) != self.num_hidden_layers:
            raise ValueError("%d windows for %d layers" % (
                len(self.sliding_windows), self.num_hidden_layers))
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("%d heads over %d key-value heads" % (
                self.num_attention_heads, self.num_key_value_heads))
        if self.head_dim % 2:
            raise ValueError("rotary needs an even head_dim, got %d"
                             % self.head_dim)
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
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("%d experts a token of %d" % (
                self.num_experts_per_tok, self.num_experts))
        if not 1 <= self.max_seq_len <= self.max_position_embeddings:
            raise ValueError(
                "context cap %d outside the model's %d positions"
                % (self.max_seq_len, self.max_position_embeddings))

    @classmethod
    def from_source(cls, source: dict, max_context: int,
                    experts_held=None):
        """The config from a published `config.json` as a dict (keys
        this family does not read are ignored), the deployment's
        context cap and the experts held here, `(first, count)`. A
        file cut to a chip's share gives the experts it HOLDS under
        `num_experts` and the router's width under
        `num_experts_published`; `experts_held` defaults to the first
        `num_experts` of them."""
        def refuse(what):
            raise ValueError("this family does not serve %s" % what)
        if source.get("scoring_func", "sigmoid") != "sigmoid":
            refuse("scoring_func %r" % source["scoring_func"])
        if source.get("n_group", 1) != 1 or source.get("topk_group", 1) != 1:
            refuse("grouped routing (n_group %r, topk_group %r)" % (
                source.get("n_group"), source.get("topk_group")))
        if source.get("hidden_act", "silu") != "silu":
            refuse("hidden_act %r" % source["hidden_act"])
        n = source["num_hidden_layers"]
        kinds = list(source["mlp_layer_types"])[:n]
        dense = next((i for i, k in enumerate(kinds) if k != "dense"), n)
        if any(k != "sparse" for k in kinds[dense:]):
            refuse("a dense layer after a sparse one: %r" % kinds)
        windows = tuple(
            int(source["sliding_window"]) if t == "sliding_attention"
            else 0 for t in list(source["layer_types"])[:n])
        if "sliding_windows" in source and \
                tuple(source["sliding_windows"][:n]) != windows:
            refuse("sliding_windows %r beside layer_types %r" % (
                source["sliding_windows"], source["layer_types"]))
        total = source.get("num_experts_published", source["num_experts"])
        first, held = experts_held or (0, source["num_experts"])
        rope = source.get("rope_parameters") or source
        same = ("vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "intermediate_size", "moe_intermediate_size",
                "num_experts_per_tok", "num_shared_experts",
                "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
                "max_position_embeddings")
        return cls(num_experts=int(total), experts_first=int(first),
                   experts_held=int(held), sliding_windows=windows,
                   dense_layers=dense,
                   rope_theta=float(rope["rope_theta"]),
                   max_seq_len=int(max_context),
                   **{k: source[k] for k in same})

    # --- the cache's geometry: what the engine sizes its pools by ------
    @property
    def kv_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def kv_row(self) -> int:
        return self.num_key_value_heads * self.head_dim

    @property
    def kv_windows(self) -> Tuple[int, ...]:
        """A window a cache layer (0: the whole context): what the
        engine's attended counters count by."""
        return self.sliding_windows

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - self.dense_layers

    def meta(self) -> dict:
        """JSON-able identity for program_cache.fn_fingerprint: every
        field changes the compiled program."""
        return dict(family="moe_window",
                    **{k: (list(v) if isinstance(v, tuple) else v)
                       for k, v in ((k, getattr(self, k))
                                    for k in self.__dataclass_fields__)})

    # --- what a step reports beside its tokens --------------------------
    @property
    def step_stats_len(self) -> int:
        """int32 numbers `forward_paged(..., live=...)` returns last:
        the tokens each held expert took in each sparse layer."""
        return self.sparse_layers * self.experts_held

    def record_step_stats(self, stats) -> None:
        """The host's half: one mixed step's routing counts (live slots
        only) into the counters (docs/observability.md)."""
        load = np.asarray(stats).reshape(self.sparse_layers,
                                         self.experts_held)
        stat_add("STAT_generation_moe_pairs", int(load.sum()))
        stat_add("STAT_generation_moe_experts_touched",
                 int((load > 0).sum()))
        stat_add("STAT_generation_moe_peak_load",
                 int(load.max(axis=1).sum()))

    # --- the seam the engine calls -------------------------------------
    def forward_full(self, params, tokens, lengths, attn_lanes: int = 0):
        return forward_full(self, params, tokens, lengths, attn_lanes)

    def forward_paged(self, params, k_pools, v_pools, block_tables,
                      ctx_lens, tokens, k_scale_pools=None,
                      v_scale_pools=None, live=None):
        if k_scale_pools is not None:
            raise ValueError("a quantized KV pool is not served by the "
                             "expert family")
        return forward_paged(self, params, k_pools, v_pools,
                             block_tables, ctx_lens, tokens, live)


def leaf_shapes(cfg: ExpertDecoderConfig) -> dict:
    """name -> (shape, fan_in | None for a unit gain | "bias"). q, k
    and v share one matrix, gate and up another; the experts' are
    stacked `[layers, experts held, ...]`."""
    h, v = cfg.hidden_size, cfg.vocab_size
    d = cfg.head_dim
    qd = cfg.num_attention_heads * d
    m = cfg.moe_intermediate_size
    ms = cfg.num_shared_experts * m
    e = cfg.experts_held

    def attn(n):
        return {"wqkv": ((n, h, qd + 2 * cfg.kv_row), h),
                "q_norm": ((n, d), None), "k_norm": ((n, d), None),
                "wo": ((n, qd, h), qd),
                "ln_attn": ((n, h), None), "ln_ffn": ((n, h), None)}
    out = {"tok_emb": ((v, h), None), "unembed": ((h, v), h),
           "norm_f": ((h,), None)}
    nd, ns = cfg.dense_layers, cfg.sparse_layers
    if nd:
        i = cfg.intermediate_size
        dense = dict(attn(nd), w_gu=((nd, h, 2 * i), h),
                     w_down=((nd, i, h), i))
        out.update({DENSE_PREFIX + k: s for k, s in dense.items()})
    if ns:
        out.update(attn(ns))
        out.update({
            "router": ((ns, h, cfg.num_experts), h),
            "router_bias": ((ns, cfg.num_experts), "bias"),
            "e_gu": ((ns, e, h, 2 * m), h), "e_down": ((ns, e, m, h), m),
            "s_gu": ((ns, h, 2 * ms), h), "s_down": ((ns, ms, h), ms)})
    return out


def init_params(cfg: ExpertDecoderConfig, seed: int = 0,
                dtype=jnp.float32) -> dict:
    """Gaussian init, numpy RNG (host-side, deterministic by seed):
    N(0, 0.02) embedding, N(0, 1/sqrt(fan_in)) matrices, unit gains, a
    choice bias N(0, 0.01): small beside the scores' spread and not
    zero, so that leaving it out changes which experts run."""
    return draw_params(leaf_shapes(cfg), seed, dtype)


def draw_params(shapes: dict, seed: int, dtype) -> dict:
    """`init_params`' draw over `shapes`, name -> (shape, fan_in | None
    for a unit gain | "bias")."""
    rng = np.random.default_rng(seed)
    p = {}
    for name, (shape, fan_in) in shapes.items():
        if name == "tok_emb":
            w = rng.normal(0.0, 0.02, shape)
        elif fan_in == "bias":
            w = rng.normal(0.0, 0.01, shape)
        elif fan_in is None:
            w = np.ones(shape)
        else:
            w = rng.normal(0.0, 1.0 / math.sqrt(fan_in), shape)
        p[name] = jnp.asarray(w, dtype=dtype)
    return p


def _qkv(cfg: ExpertDecoderConfig, w, x, cos, sin, window):
    """x `[..., h]` -> q `[..., heads, D]`, k, v `[..., kv_heads, D]`:
    q and k normed per head, and rotated to the token's position in a
    window layer (`window` > 0, a traced scalar), left as they are in
    a full one."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("qkv"):
        qkv = _mm(x, w["wqkv"])
        qd = cfg.num_attention_heads * cfg.head_dim
        lead = x.shape[:-1]
        q = qkv[..., :qd].reshape(lead + (cfg.num_attention_heads,
                                          cfg.head_dim))
        k = qkv[..., qd:qd + cfg.kv_row].reshape(
            lead + (cfg.kv_heads, cfg.head_dim))
        v = qkv[..., qd + cfg.kv_row:].reshape(
            lead + (cfg.kv_heads, cfg.head_dim))
        q = _rms(q, w["q_norm"], eps)
        k = _rms(k, w["k_norm"], eps)
        with jax.named_scope("rope"):
            rotary = window > 0
            q = jnp.where(rotary, _rope(q, cos, sin), q)
            k = jnp.where(rotary, _rope(k, cos, sin), k)
    return q, k, v


def _gated(x, w_gu, w_down):
    gu = _mm(x, w_gu)
    i = w_gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :i]) * gu[..., i:], w_down)


def route(cfg: ExpertDecoderConfig, w, x):
    """x `[T, h]` float32 -> (chosen `[T, k]` int32 among ALL experts,
    their weights `[T, k]` float32). Scores in float32 at precision
    `highest`; the bias decides the choice only; the weights are
    normalised over all k chosen, held here or not."""
    s = jax.nn.sigmoid(jnp.dot(
        x, w["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    _, chosen = jax.lax.top_k(s + w["router_bias"].astype(jnp.float32),
                              cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.norm_topk_prob:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), picked * cfg.routed_scaling_factor


def moe(cfg: ExpertDecoderConfig, w, x, live):
    """The sparse feed-forward on x `[T, h]` float32 -> (`[T, h]`,
    the tokens each held expert took `[experts_held]` int32, slots
    with `live` False not counted and not computed). `w`: the layer's
    leaves, the experts' WHOLE (`[sparse layers, experts, ...]`) with
    `w["at"]` the sparse layer this is."""
    scope = jax.named_scope
    t, h = x.shape
    k, e = cfg.num_experts_per_tok, cfg.experts_held
    e_gu = w["e_gu"].reshape((-1,) + w["e_gu"].shape[2:])
    e_down = w["e_down"].reshape((-1,) + w["e_down"].shape[2:])
    with scope("moe"):
        with scope("moe_router"):
            chosen, weights = route(cfg, w, x)
            local = chosen - cfg.experts_first
            held = (local >= 0) & (local < e) & live[:, None]
            # pairs of one expert side by side, expert by expert; the
            # pairs this chip does not compute (key `e`) last
            key = jnp.where(held, local, e).reshape(t * k)
            order = jnp.argsort(key, stable=True)
            load = jnp.sum(key[:, None] == jnp.arange(e, dtype=jnp.int32),
                           axis=0, dtype=jnp.int32)            # [E]
            token = (order // k).astype(jnp.int32)
            scale = jnp.where(held, weights, 0.0).reshape(t * k)[order]
            # this layer's experts among all the leaf's groups
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((e_gu.shape[0],), jnp.int32), load,
                (w["at"] * e,))
        with scope("moe_experts"):
            xs = x.astype(e_gu.dtype)[token]                   # [T k, h]
            gu = jax.lax.ragged_dot(xs, e_gu, groups,
                                    preferred_element_type=jnp.float32)
            m = e_gu.shape[-1] // 2
            act = jax.nn.silu(gu[:, :m]) * gu[:, m:]
            y = jax.lax.ragged_dot(act.astype(e_down.dtype), e_down,
                                   groups,
                                   preferred_element_type=jnp.float32)
            # a row past the last group belongs to no expert (its scale
            # is 0): whatever the grouped product left there is not read
            y = jnp.where(scale[:, None] > 0, y * scale[:, None], 0.0)
            out = jnp.zeros((t, h), jnp.float32).at[token].add(y)
        with scope("moe_shared"):
            out = out + _gated(x, w["s_gu"], w["s_down"])
    return out, load


def _ffn(cfg: ExpertDecoderConfig, w, x, live):
    """The layer's feed-forward on x `[..., h]`: dense where the
    layer's leaves hold `w_gu`, routed where they hold a router.
    -> (branch output, the held experts' load or None)."""
    if "w_gu" in w:
        with jax.named_scope("mlp"):
            return _gated(x, w["w_gu"], w["w_down"]), None
    flat = x.reshape(-1, x.shape[-1])
    out, load = moe(cfg, w, flat, live.reshape(-1))
    return out.reshape(x.shape), load


def _after_attention(cfg: ExpertDecoderConfig, w, x, o, live):
    """The rest of a layer: o `[..., heads * D]` merged heads."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("attn_out"):
        x = x + _rms(_mm(o, w["wo"]), w["ln_attn"], eps)
    m, load = _ffn(cfg, w, x, live)
    return x + _rms(m, w["ln_ffn"], eps), load


def _stacks(cfg, params, layer, carry,
            leaves=(DENSE_LEAVES, SPARSE_LEAVES)):
    """`carry, y = layer(carry, layer's weights, cache slot, window)`
    over the leading dense layers, then over the sparse ones: a
    `lax.scan` each, the slot and the window scanned beside the
    weights; `leaves` names the two kinds' stacked leaves (the latent
    family's, generation/mla_moe.py, are not these). -> (carry, [what
    each scan emitted, stacked by layer])."""
    windows = np.asarray(cfg.kv_windows, np.int32)
    nd = cfg.dense_layers
    emitted = []
    for lo, hi, names, prefix in (
            (0, nd, leaves[0], DENSE_PREFIX),
            (nd, cfg.num_hidden_layers, leaves[1], "")):
        if hi == lo:
            continue
        stack = {n: params[prefix + n] for n in names}
        whole = {n: params[n] for n in EXPERT_LEAVES} if lo == nd else {}
        xs = (stack, jnp.arange(lo, hi, dtype=jnp.int32),
              jnp.asarray(windows[lo:hi]))
        carry, ys = jax.lax.scan(
            lambda c, x, lo=lo, whole=whole: layer(
                c, dict(x[0], at=x[1] - lo, **whole), x[1], x[2]),
            carry, xs)
        emitted.append(ys)
    return carry, emitted


def forward_full(cfg: ExpertDecoderConfig, params: dict, tokens,
                 lengths, attn_lanes: int = 0):
    """Full-context forward, model.forward_full's contract: tokens
    `[B, S]`, lengths `[B]` -> (logits `[B, vocab]` at position
    lengths-1, k_cache, v_cache each `[kv_layers, B, S, kv_heads,
    head_dim]`). `attn_lanes` pads the key axis to the paged path's
    lane count (looped.forward_full)."""
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
    base = causal & visible[:, None, :]                    # [B, S, L]
    back = pos[:, None] - kpos[None, :]                    # i - j
    live = pos[None, :] < lengths[:, None]                 # [B, S]
    pad = ((0, 0), (0, lanes - s), (0, 0), (0, 0))
    rep = cfg.num_attention_heads // cfg.kv_heads
    sm_scale = 1.0 / math.sqrt(cfg.head_dim)

    def layer(carry, w, slot, window):
        x, = carry
        q, k, v = _qkv(cfg, w, x, cos, sin, window)        # [B,S,H,D]
        mask = (base & ((window <= 0) | (back < window)[None]))[:, None]

        def keys(t):                                       # [B,H,L,D]
            return jnp.repeat(jnp.pad(t, pad), rep,
                              axis=2).transpose(0, 2, 1, 3)
        o = attend_reference(q.transpose(0, 2, 1, 3), keys(k), keys(v),
                             mask, sm_scale)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, -1)
        x, _ = _after_attention(cfg, w, x, o, live)
        return (x,), (k, v)
    (x,), emitted = _stacks(cfg, params, layer, (x,))
    ks = jnp.concatenate([e[0] for e in emitted], axis=0)
    vs = jnp.concatenate([e[1] for e in emitted], axis=0)
    x = _rms(x, params["norm_f"], cfg.rms_norm_eps)
    logits = _mm(x, params["unembed"])                     # [B, S, V]
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None].astype(jnp.int32),
        axis=1)[:, 0]
    return last, ks, vs


def forward_paged(cfg: ExpertDecoderConfig, params: dict, k_pools,
                  v_pools, block_tables, ctx_lens, tokens, live=None):
    """The engine's mixed step, model.forward_paged's contract: tokens
    `[B]` (each slot's token at position ctx_lens), pools `[kv_layers,
    N, bs, kv_heads * head_dim]` -> (logits `[B, vocab]`, the pools
    with this step's rows written) and, where `live` `[B]` bool says
    which slots carry a token, last the held experts' loads `[sparse
    layers, experts_held]` int32 over those slots (an idle slot's
    pairs are not computed either)."""
    scope = jax.named_scope
    b = tokens.shape[0]
    bs = k_pools.shape[2]
    count = live is not None
    if live is None:
        live = jnp.ones((b,), bool)
    with scope("embed"):
        x = params["tok_emb"][tokens].astype(jnp.float32)  # [B, h]
        cos, sin = _rope_tables(cfg, ctx_lens)             # [B, D]
    sm_scale = 1.0 / math.sqrt(cfg.head_dim)
    with scope("kv_write"):
        blk = jnp.take_along_axis(
            block_tables, (ctx_lens // bs)[:, None].astype(jnp.int32),
            axis=1)[:, 0]                                  # [B]
        off = ctx_lens % bs
    row = (b,) + k_pools.shape[3:]          # a slot's K or V, as stored

    def layer(carry, w, slot, window):
        x, kp, vp = carry
        q, k, v = _qkv(cfg, w, x, cos, sin, window)        # [B,H,D]
        with scope("kv_write"):
            kp = kp.at[slot, blk, off].set(
                k.reshape(row).astype(kp.dtype))
            vp = vp.at[slot, blk, off].set(
                v.reshape(row).astype(vp.dtype))
        o = paged_attention(q, kp, vp, block_tables, ctx_lens + 1,
                            sm_scale=sm_scale, layer=slot,
                            window=window)                 # [B,H,D]
        x, load = _after_attention(cfg, w, x, o.reshape(b, -1), live)
        return (x, kp, vp), load
    (x, kp, vp), emitted = _stacks(cfg, params, layer,
                                   (x, k_pools, v_pools))
    with scope("unembed"):
        x = _rms(x, params["norm_f"], cfg.rms_norm_eps)
        logits = _mm(x, params["unembed"])                 # [B, V]
    if not count:
        return logits, kp, vp
    loads = emitted[-1] if cfg.sparse_layers else \
        jnp.zeros((0, cfg.experts_held), jnp.int32)
    return logits, kp, vp, loads
