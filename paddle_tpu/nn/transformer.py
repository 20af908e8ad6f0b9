"""Transformer layers: MultiHeadAttention, encoder/decoder stacks.

Analog of /root/reference/python/paddle/nn/layer/transformer.py
(MultiHeadAttention, TransformerEncoderLayer, TransformerEncoder) and of
the reference's fused attention op
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu).
The attention core routes to the Pallas flash-attention kernel
(paddle_tpu/kernels/flash_attention.py) on TPU when enabled; otherwise a
composed einsum path that XLA fuses.
"""
from __future__ import annotations

import math
from typing import Optional

from ..core.program import in_dygraph_mode
from ..dygraph import tape
from ..dygraph.tape import Tensor
from . import functional as F
from .layer import Layer, LayerList
from .layers_lib import Dropout, LayerNorm, Linear

_USE_FLASH = True


def set_flash_attention(enabled: bool):
    global _USE_FLASH
    _USE_FLASH = enabled


# Routing points measured on v5e (B=32,H=12,D=64, bf16):
# - WITHOUT dropout (eval/inference): composed wins at S=512 (~2.8ms vs
#   ~4ms f+b — the score tile fits HBM traffic easily); flash pays from
#   S>=1024 where the materialized probs dominate.
# - WITH dropout (training, the benchmark's scored config, re-measured
#   round 5 with a padding mask, fwd+bwd): flash+in-kernel-dropout
#   8.54ms vs flash+HBM-mask 12.71ms vs composed 13.21ms at S=512 —
#   any flash variant wins once the composed path must materialize the
#   [B,H,S,S] keep-mask, and flash keeps winning at 1024 (0.74x) and
#   2048 (0.90x) (scripts/tpu_experiments.py sections 2/2b).
_FLASH_MIN_SEQ = 1024          # no-dropout crossover
_FLASH_MIN_SEQ_DROPOUT = 512   # dropout-active crossover

# trace-time record of which attention path ACTUALLY lowered (the
# round-2 postmortem: a bench must never infer the path from config —
# it reads this log, written at the moment of routing)
_PATH_LOG = []


def reset_attention_path_log():
    del _PATH_LOG[:]


def attention_paths_taken():
    return list(_PATH_LOG)


def routes_to_flash(seq_len: int, head_dim: int,
                    dropout_active: bool = False) -> bool:
    """The router's own predicate (kept next to it so they cannot
    drift): whether _attention_core will attempt the Pallas kernel.
    dropout_active lowers the crossover to _FLASH_MIN_SEQ_DROPOUT —
    once the composed path must materialize a [B,H,S,S] keep-mask,
    flash wins from shorter sequences (round-5 measurement above)."""
    import jax
    from ..kernels import gspmd_will_partition
    min_seq = _FLASH_MIN_SEQ_DROPOUT if dropout_active else _FLASH_MIN_SEQ
    return (_USE_FLASH and jax.default_backend() == "tpu"
            and seq_len >= min_seq and head_dim in (64, 128, 256)
            and not gspmd_will_partition())


def _attention_core(q, k, v, attn_mask, dropout_p, training, is_causal=False):
    """q,k,v: [B, S, H, D] raw jax arrays -> [B, S, H, D].

    Layout note: inputs stay in the projection layout [B,S,H,D]; the
    einsums put the head axis where the dot needs it WITHOUT materializing
    [B,H,S,D] transposes (XLA folds the layout into the matmul — the
    explicit-transpose version showed up as 7.7% "data formatting" in the
    TPU profile).

    Routing: the composed path wins below _FLASH_MIN_SEQ — at short S the
    score tile fits HBM traffic easily and XLA's batched matmuls amortize
    the chip's fixed per-matmul cost better than many small Pallas
    programs. The Pallas flash kernel takes over at long S where the
    O(S^2) score matrix must stay out of HBM. Attention-probs dropout
    runs inside the kernel from a precomputed keep-mask, so the flash
    path covers real training configs (BERT's default
    attention_probs_dropout_prob=0.1 included).

    A kernel error propagates by default; set
    FLAGS_flash_attention_fallback=True to instead log once and use the
    composed path (never silent — see round-2 postmortem)."""
    import jax
    import jax.numpy as jnp
    scale = 1.0 / math.sqrt(q.shape[-1])
    want_dropout = bool(dropout_p) and training
    if attn_mask is not None:
        # attn_mask is a padding/visibility mask derived from input ids
        # — non-differentiable by contract (matching the reference's
        # usage; a LEARNABLE attention bias should call the functional
        # flash_attention with bias_needs_grad=True instead). Making it
        # explicit here lets the flash path skip the dbias recompute
        # and keeps the in-kernel dropout path eligible.
        attn_mask = jax.lax.stop_gradient(attn_mask)
    if routes_to_flash(q.shape[1], q.shape[-1], dropout_active=want_dropout):
        try:
            from ..kernels.flash_attention import flash_attention
            rng = tape._state.next_key() if want_dropout else None
            out = flash_attention(
                jnp.transpose(q, (0, 2, 1, 3)),
                jnp.transpose(k, (0, 2, 1, 3)),
                jnp.transpose(v, (0, 2, 1, 3)),
                bias=attn_mask, causal=is_causal, sm_scale=scale,
                dropout_rate=float(dropout_p) if want_dropout else 0.0,
                dropout_rng=rng, bias_needs_grad=False)
            _PATH_LOG.append("flash")
            return jnp.transpose(out, (0, 2, 1, 3))
        except Exception:
            from .. import flags as _flags
            if not _flags.get_flag("FLAGS_flash_attention_fallback",
                                   False):
                raise
            import logging
            logging.getLogger("paddle_tpu").warning(
                "flash_attention failed; composed-attention fallback "
                "is active (FLAGS_flash_attention_fallback=True)",
                exc_info=True)
    _PATH_LOG.append("composed")
    # `attention` on the device trace, where the Pallas kernel's calls
    # read `flash_attention` (telemetry.py's convention)
    with jax.named_scope("attention"):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if attn_mask is not None:
            scores = scores + attn_mask
        if is_causal:
            s = scores.shape[-1]
            causal = jnp.tril(jnp.ones((s, s), bool))
            scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores, axis=-1)
        if want_dropout:
            # the [B,H,Sq,Sk] keep decision is the composed path's biggest
            # backward residual; apply_probs_dropout honors
            # FLAGS_dropout_storage (u8 = 1 byte/elem, seed = key-only)
            # through the same dispatch the dropout op uses
            from ..ops.nn import apply_probs_dropout
            probs = apply_probs_dropout(probs, 1.0 - dropout_p,
                                        tape._state.next_key())
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(probs.dtype))


class MultiHeadAttention(Layer):
    """paddle.nn.MultiHeadAttention analog (transformer.py:88)."""

    def __init__(self, embed_dim: int, num_heads: int, dropout: float = 0.0,
                 kdim: Optional[int] = None, vdim: Optional[int] = None,
                 need_weights: bool = False, weight_attr=None,
                 bias_attr=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim
        self.dropout = dropout
        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(kdim or embed_dim, embed_dim, weight_attr,
                             bias_attr)
        self.v_proj = Linear(vdim or embed_dim, embed_dim, weight_attr,
                             bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def forward(self, query, key=None, value=None, attn_mask=None,
                is_causal: bool = False):
        import jax.numpy as jnp
        h, d = self.num_heads, self.head_dim
        mask_v = None
        if attn_mask is not None:
            mask_v = attn_mask.value if isinstance(attn_mask, Tensor) \
                else attn_mask

        self_attn = key is None and value is None and \
            self.k_proj.weight.shape == self.q_proj.weight.shape and \
            all(p.bias is not None for p in (self.q_proj, self.k_proj,
                                             self.v_proj))
        if self_attn:
            # under a device mesh the fused path is WRONG: the XLA SPMD
            # partitioner miscompiles concatenate along a sharded dim
            # (observed on CPU: outputs scaled by the replicated-axis
            # size), and the fused QKV concat runs along exactly the dim
            # Megatron-style rules shard (P(None, "mp")). The unfused
            # three-matmul path partitions exactly, and under SPMD the
            # one-big-matmul fusion dissolves into per-shard matmuls
            # anyway. Trace-time check: TrainStep/Executor activate
            # their ShardingPlan while tracing, and init_parallel_env
            # sets the env mesh, so get_mesh() sees both.
            from ..parallel.env import get_mesh
            mesh = get_mesh()
            if mesh is not None and mesh.size > 1:
                self_attn = False
        if self_attn:
            # fused QKV: ONE [E, 3E] matmul instead of three — the chip
            # pays a fixed cost per matmul op, so fewer+bigger wins; the
            # parameters stay separate (state-dict parity with the
            # reference's q/k/v_proj) and concat/split trace into the
            # graph, grads flowing back through the slices
            def core(x, wq, wk, wv, bq, bk, bv):
                b, sq, _ = x.shape
                # apply_fn bypasses the tape's per-op autocast, so honor
                # the AMP policy here: without this the fused QKV matmul
                # AND the flash kernel run fp32 (half MXU rate, double
                # VMEM traffic)
                if tape._state.amp_dtype is not None:
                    from ..core.dtypes import to_jax_dtype
                    amp_jdt = to_jax_dtype(tape._state.amp_dtype)
                    x, wq, wk, wv, bq, bk, bv = (
                        t.astype(amp_jdt)
                        for t in (x, wq, wk, wv, bq, bk, bv))
                w = jnp.concatenate([wq, wk, wv], axis=1)
                bias = jnp.concatenate([bq, bk, bv])
                qkv = x @ w + bias
                qx, kx, vx = jnp.split(qkv, 3, axis=-1)
                out = _attention_core(
                    qx.reshape(b, sq, h, d), kx.reshape(b, sq, h, d),
                    vx.reshape(b, sq, h, d), mask_v, self.dropout,
                    self.training, is_causal)
                return [out.reshape(b, sq, self.embed_dim)]

            out = tape.apply_fn(
                core, query, self.q_proj.weight, self.k_proj.weight,
                self.v_proj.weight, self.q_proj.bias, self.k_proj.bias,
                self.v_proj.bias)[0]
            return self.out_proj(out)

        key = query if key is None else key
        value = query if value is None else value
        q = self.q_proj(query)
        k = self.k_proj(key)
        v = self.v_proj(value)

        def core(qx, kx, vx):
            b, sq, _ = qx.shape
            sk = kx.shape[1]
            out = _attention_core(qx.reshape(b, sq, h, d),
                                  kx.reshape(b, sk, h, d),
                                  vx.reshape(b, sk, h, d), mask_v,
                                  self.dropout, self.training, is_causal)
            return [out.reshape(b, sq, self.embed_dim)]

        out = tape.apply_fn(core, q, k, v)[0]
        return self.out_proj(out)


class TransformerEncoderLayer(Layer):
    """paddle.nn.TransformerEncoderLayer analog (transformer.py:585)."""

    def __init__(self, d_model: int, nhead: int, dim_feedforward: int,
                 dropout: float = 0.1, activation: str = "gelu",
                 attn_dropout: Optional[float] = None,
                 act_dropout: Optional[float] = None,
                 normalize_before: bool = False):
        super().__init__()
        self.self_attn = MultiHeadAttention(
            d_model, nhead,
            dropout if attn_dropout is None else attn_dropout)
        self.linear1 = Linear(d_model, dim_feedforward)
        self.linear2 = Linear(dim_feedforward, d_model)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout = Dropout(dropout)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(
            dropout if act_dropout is None else act_dropout)
        self.activation = activation
        self.normalize_before = normalize_before

    def forward(self, src, src_mask=None):
        residual = src
        if self.normalize_before:
            src = self.norm1(src)
        src = self.self_attn(src, attn_mask=src_mask)
        src = residual + self.dropout1(src)
        if not self.normalize_before:
            src = self.norm1(src)
        residual = src
        if self.normalize_before:
            src = self.norm2(src)
        act = getattr(F, self.activation)
        src = self.linear2(self.dropout2(act(self.linear1(src))))
        src = residual + self.dropout(src)
        if not self.normalize_before:
            src = self.norm2(src)
        return src


class TransformerEncoder(Layer):
    def __init__(self, encoder_layer_fn, num_layers: int, norm=None):
        super().__init__()
        self.layers = LayerList([encoder_layer_fn()
                                 for _ in range(num_layers)])
        self.norm = norm  # __setattr__ registers the sublayer

    def forward(self, src, src_mask=None):
        out = src
        for layer in self.layers:
            out = layer(out, src_mask)
        if self.norm is not None:
            out = self.norm(out)
        return out


class TransformerDecoderLayer(Layer):
    """paddle.nn.TransformerDecoderLayer (transformer.py:858): causal
    self-attention, cross-attention over encoder memory, ffn — each
    with residual + LayerNorm (post-norm default)."""

    def __init__(self, d_model: int, nhead: int, dim_feedforward: int,
                 dropout: float = 0.1, activation: str = "gelu",
                 attn_dropout: Optional[float] = None,
                 act_dropout: Optional[float] = None,
                 normalize_before: bool = False):
        super().__init__()
        adp = dropout if attn_dropout is None else attn_dropout
        self.self_attn = MultiHeadAttention(d_model, nhead, adp)
        self.cross_attn = MultiHeadAttention(d_model, nhead, adp)
        self.linear1 = Linear(d_model, dim_feedforward)
        self.linear2 = Linear(dim_feedforward, d_model)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout = Dropout(dropout)
        self.dropout1 = Dropout(dropout)
        self.dropout2 = Dropout(dropout)
        self.dropout3 = Dropout(
            dropout if act_dropout is None else act_dropout)
        self.activation = activation
        self.normalize_before = normalize_before

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None):
        # parity: only the caller-supplied tgt_mask applies (paddle's
        # decoder layer never forces causality — autoregressive users
        # pass Transformer.generate_square_subsequent_mask)
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        tgt = self.self_attn(tgt, attn_mask=tgt_mask)
        tgt = residual + self.dropout1(tgt)
        if not self.normalize_before:
            tgt = self.norm1(tgt)

        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        tgt = self.cross_attn(tgt, memory, memory,
                              attn_mask=memory_mask)
        tgt = residual + self.dropout2(tgt)
        if not self.normalize_before:
            tgt = self.norm2(tgt)

        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        act = getattr(F, self.activation)
        tgt = self.linear2(self.dropout3(act(self.linear1(tgt))))
        tgt = residual + self.dropout(tgt)
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        return tgt


class TransformerDecoder(Layer):
    def __init__(self, decoder_layer_fn, num_layers: int, norm=None):
        super().__init__()
        self.layers = LayerList([decoder_layer_fn()
                                 for _ in range(num_layers)])
        self.norm = norm  # __setattr__ registers the sublayer

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None):
        out = tgt
        for layer in self.layers:
            out = layer(out, memory, tgt_mask, memory_mask)
        if self.norm is not None:
            out = self.norm(out)
        return out


class Transformer(Layer):
    """paddle.nn.Transformer (transformer.py:1086): full
    encoder-decoder. Embeddings/heads live outside, like the
    reference."""

    def __init__(self, d_model: int = 512, nhead: int = 8,
                 num_encoder_layers: int = 6, num_decoder_layers: int = 6,
                 dim_feedforward: int = 2048, dropout: float = 0.1,
                 activation: str = "relu",
                 attn_dropout: Optional[float] = None,
                 act_dropout: Optional[float] = None,
                 normalize_before: bool = False):
        super().__init__()
        self.encoder = TransformerEncoder(
            lambda: TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before),
            num_encoder_layers)
        self.decoder = TransformerDecoder(
            lambda: TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before),
            num_decoder_layers)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask)
        return self.decoder(tgt, memory, tgt_mask, memory_mask)

    @staticmethod
    def generate_square_subsequent_mask(length: int):
        """paddle.nn.Transformer.generate_square_subsequent_mask:
        additive [L, L] mask, -inf above the diagonal."""
        import numpy as np
        m = np.triu(np.full((length, length), -np.inf, np.float32), 1)
        return Tensor(m)
