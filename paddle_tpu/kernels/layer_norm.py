"""Fused layer normalization for TPU (Pallas), with custom VJP.

TPU-native equivalent of /root/reference/paddle/fluid/operators/
layer_norm_op.cu (fused mean/var/normalize/affine in one kernel) — here
one VMEM-resident pass per row-block; the backward accumulates dgamma /
dbeta across the sequential TPU grid into a single output block instead
of the reference's two-stage block reduction.

x: [..., F] normalized over the trailing dim; gamma/beta: [F].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import gspmd_will_partition


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def layer_norm_reference(x, gamma, beta, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    xhat = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (xhat * gamma.astype(jnp.float32) +
            beta.astype(jnp.float32)).astype(x.dtype)


def _fwd_kernel(x_ref, g_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=1)
    xc = x - mean[:, None]
    var = jnp.mean(xc * xc, axis=1)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd[:, None]
    o_ref[:] = (xhat * g_ref[:].astype(jnp.float32)[None, :] +
                b_ref[:].astype(jnp.float32)[None, :]).astype(o_ref.dtype)
    mean_ref[:] = mean[:, None]  # [blk, 1] trailing-lane layout
    rstd_ref[:] = rstd[:, None]


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, do_ref,
                dx_ref, dg_ref, db_ref):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    mean = mean_ref[:, 0]
    rstd = rstd_ref[:, 0]
    xhat = (x - mean[:, None]) * rstd[:, None]
    wdo = do * g[None, :]
    c1 = jnp.mean(wdo, axis=1)
    c2 = jnp.mean(wdo * xhat, axis=1)
    dx = (wdo - c1[:, None] - xhat * c2[:, None]) * rstd[:, None]
    dx_ref[:] = dx.astype(dx_ref.dtype)

    # TPU grid steps run sequentially: accumulate dgamma/dbeta in-place
    partial_dg = jnp.sum(do * xhat, axis=0)
    partial_db = jnp.sum(do, axis=0)

    @pl.when(i == 0)
    def _():
        dg_ref[:] = partial_dg
        db_ref[:] = partial_db

    @pl.when(i > 0)
    def _():
        dg_ref[:] = dg_ref[:] + partial_dg
        db_ref[:] = db_ref[:] + partial_db


def _pick_block(rows: int) -> int:
    for blk in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if rows % blk == 0:
            return blk
    return 1


def _fwd(x, gamma, beta, eps, interpret):
    orig_shape = x.shape
    f = orig_shape[-1]
    rows = x.size // f
    x2 = x.reshape(rows, f)
    blk = _pick_block(rows)
    grid = (rows // blk,)
    # named for the device trace (telemetry.py's convention)
    with jax.named_scope("layer_norm"):
        o, mean, rstd = pl.pallas_call(
            functools.partial(_fwd_kernel, eps=eps),
            grid=grid,
            in_specs=[pl.BlockSpec((blk, f), lambda i: (i, 0)),
                      pl.BlockSpec((f,), lambda i: (0,)),
                      pl.BlockSpec((f,), lambda i: (0,))],
            out_specs=[pl.BlockSpec((blk, f), lambda i: (i, 0)),
                       pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                       pl.BlockSpec((blk, 1), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((rows, f), x.dtype),
                       jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                       jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
            interpret=interpret,
            name="layer_norm_fwd",
        )(x2, gamma, beta)
    return o.reshape(orig_shape), (x2, gamma, mean, rstd, orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layer_norm(x, gamma, beta, eps, interpret):
    o, _ = _fwd(x, gamma, beta, eps, interpret)
    return o


def _layer_norm_fwd(x, gamma, beta, eps, interpret):
    return _fwd(x, gamma, beta, eps, interpret)


def _layer_norm_bwd(eps, interpret, res, g):
    x2, gamma, mean, rstd, orig_shape = res
    f = x2.shape[1]
    rows = x2.shape[0]
    do2 = g.reshape(rows, f)
    blk = _pick_block(rows)
    with jax.named_scope("layer_norm"):
        dx, dg, db = pl.pallas_call(
            _bwd_kernel,
            grid=(rows // blk,),
            in_specs=[pl.BlockSpec((blk, f), lambda i: (i, 0)),
                      pl.BlockSpec((f,), lambda i: (0,)),
                      pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                      pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                      pl.BlockSpec((blk, f), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((blk, f), lambda i: (i, 0)),
                       pl.BlockSpec((f,), lambda i: (0,)),
                       pl.BlockSpec((f,), lambda i: (0,))],
            out_shape=[jax.ShapeDtypeStruct((rows, f), x2.dtype),
                       jax.ShapeDtypeStruct((f,), jnp.float32),
                       jax.ShapeDtypeStruct((f,), jnp.float32)],
            interpret=interpret,
            name="layer_norm_bwd",
        )(x2, gamma, mean, rstd, do2)
    return (dx.reshape(orig_shape), dg.astype(gamma.dtype),
            db.astype(gamma.dtype))


_layer_norm.defvjp(_layer_norm_fwd, _layer_norm_bwd)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    """Fused layer norm over the trailing dim. Falls back to the composed
    XLA path when the feature dim is not lane-aligned, or when GSPMD
    will partition the step (kernels.gspmd_will_partition)."""
    f = x.shape[-1]
    rows = x.size // f
    if f % 128 != 0 or rows % 8 != 0 or gspmd_will_partition():
        return layer_norm_reference(x, gamma, beta, eps)
    return _layer_norm(x, gamma, beta, eps, _use_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layer_norm_stats(x, gamma, beta, eps, interpret):
    (y, mean, var), _ = _layer_norm_stats_fwd(x, gamma, beta, eps, interpret)
    return y, mean, var


def _layer_norm_stats_fwd(x, gamma, beta, eps, interpret):
    y, res = _fwd(x, gamma, beta, eps, interpret)
    mean, rstd = res[2].reshape(-1), res[3].reshape(-1)
    var = 1.0 / (rstd * rstd) - eps
    return (y, mean, var), res


def _layer_norm_stats_bwd(eps, interpret, res, g):
    gy, _, _ = g  # stats are saved aux in the reference; no grad through
    return _layer_norm_bwd(eps, interpret, res, gy)


_layer_norm_stats.defvjp(_layer_norm_stats_fwd, _layer_norm_stats_bwd)


def layer_norm_with_stats(x, gamma, beta, eps: float = 1e-5):
    """Like layer_norm but also returns (mean, variance) flattened over the
    leading dims — the reference op's Mean/Variance outputs
    (layer_norm_op.cc). Stats come out of the same kernel pass; no extra
    reductions over x. Gradient flows only through y."""
    f = x.shape[-1]
    if f % 128 != 0 or (x.size // f) % 8 != 0 or gspmd_will_partition():
        xf = x.astype(jnp.float32)
        mean = xf.mean(-1)
        var = ((xf - mean[..., None]) ** 2).mean(-1)
        return (layer_norm_reference(x, gamma, beta, eps),
                mean.reshape(-1), var.reshape(-1))
    return _layer_norm_stats(x, gamma, beta, eps, _use_interpret())
