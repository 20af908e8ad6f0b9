"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

First-class long-context support (absent in the reference — SURVEY.md §5
notes v1.8 predates it; its longest-sequence tools were LoD ragged
batching and recompute). Two standard schemes over the mesh 'sp' axis:

- ring_attention: Q stays put, K/V blocks rotate around the ring via
  lax.ppermute while an online-softmax accumulator (the same
  recurrence as kernels/flash_attention.py, at the shard level) folds
  in one block per step. Memory per device is O(S/n) and the KV
  transfer overlaps compute on ICI.
- ulysses_attention: all-to-all re-partitions [B, H/n, S, D] <->
  [B, H, S/n, D] so each device computes full-sequence attention for a
  head subset (DeepSpeed-Ulysses scheme); cheaper at moderate S, needs
  H % n == 0.

Both are differentiable (grad of ppermute is the reverse permute; grad
of all_to_all is all_to_all back) and compose with the dp/mp axes.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..mesh.compat import pcast as _pcast, shard_map as _shard_map
from .env import SP_AXIS


def _online_block(q, k_blk, v_blk, acc, m, l, sm_scale, mask=None):
    """Fold one K/V block into the running (acc, m, l) softmax state.
    q: [B,H,Sq,D]; k_blk/v_blk: [B,H,Sk,D]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk).astype(jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
    return acc_new, m_new, l_new


def ring_attention(q, k, v, mesh: Mesh, axis: str = SP_AXIS,
                   causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Attention over a sequence sharded on `axis`.

    q, k, v: [B, H, S, D] *global* arrays (sharded or shardable on S).
    Returns [B, H, S, D] with the same sharding. Inside, each device
    holds S/n query rows and rotates K/V shards n times.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = mesh.shape[axis]
    S = q.shape[2]
    assert S % n == 0, (S, n)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(q_l, k_l, v_l):
        # local shapes [B, H, S/n, D]
        my = jax.lax.axis_index(axis)
        s_loc = q_l.shape[2]
        # device-varying initial accumulators (jax>=0.9 shard_map vma)
        acc = _pcast(jnp.zeros(q_l.shape, jnp.float32), (axis,),
                            to="varying")
        m = _pcast(jnp.full(q_l.shape[:3], -1e30, jnp.float32),
                          (axis,), to="varying")
        l = _pcast(jnp.zeros(q_l.shape[:3], jnp.float32), (axis,),
                          to="varying")

        def step(carry, i):
            acc, m, l, k_cur, v_cur = carry
            # k_cur currently holds the shard that started on device
            # (my - i) mod n
            src = (my - i) % n
            if causal:
                def compute(args):
                    acc, m, l = args
                    q_pos = my * s_loc + jnp.arange(s_loc)[:, None]
                    k_pos = src * s_loc + jnp.arange(s_loc)[None, :]
                    mask = (q_pos >= k_pos)[None, None]
                    return _online_block(q_l, k_cur, v_cur, acc, m, l,
                                         sm_scale, mask)

                # a K/V shard strictly in this device's future (src > my)
                # is FULLY masked: skip the whole score/PV block. The
                # predicate is per-device (divergent branches are fine —
                # no collective inside; the ppermutes below run
                # unconditionally on every device). Saves ~(n-1)/2n of
                # the causal schedule's FLOPs, the shard-level analog of
                # the flash kernel's nk_live loop bound.
                acc, m, l = jax.lax.cond(src <= my, compute,
                                         lambda args: args, (acc, m, l))
            else:
                acc, m, l = _online_block(q_l, k_cur, v_cur, acc, m, l,
                                          sm_scale, None)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return (acc, m, l, k_nxt, v_nxt), None

        (acc, m, l, _, _), _ = jax.lax.scan(
            step, (acc, m, l, k_l, v_l), jnp.arange(n))
        l = jnp.maximum(l, 1e-30)
        return (acc / l[..., None]).astype(q_l.dtype)

    return _shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None),
    )(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = SP_AXIS,
                      causal: bool = False,
                      sm_scale: Optional[float] = None):
    """All-to-all sequence parallelism: trade the S shard for an H shard,
    run full-sequence attention per head subset, trade back."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = mesh.shape[axis]
    B, H, S, D = q.shape
    assert H % n == 0 and S % n == 0, (H, S, n)

    def body(q_l, k_l, v_l):
        # local [B, H, S/n, D] -> [B, H/n, S, D]
        def seq2head(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        def head2seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        qh, kh, vh = seq2head(q_l), seq2head(k_l), seq2head(v_l)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh).astype(jnp.float32) \
            * sm_scale
        if causal:
            mask = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p,
                       vh.astype(jnp.float32)).astype(q_l.dtype)
        return head2seq(o)

    return _shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None),
    )(q, k, v)
