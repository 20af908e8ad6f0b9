"""The jax surface the SPMD runtime is written against, in one place.

Written for the installed jax (0.9): top-level ``jax.shard_map`` with
``check_vma=``, ``jax.lax.axis_size``, ``jax.lax.pcast`` and
``jax.typeof`` for varying-manual-axes (VMA) typing. Every shard_map
call site in paddle_tpu imports these names from here, so the next jax
upgrade has one module to read.
"""
from __future__ import annotations

from typing import Any, Optional

import jax

axis_size = jax.lax.axis_size
pcast = jax.lax.pcast
typeof = jax.typeof


def shard_map(f, mesh, in_specs, out_specs, check_vma: Optional[bool] = None,
              **kwargs: Any):
    """``jax.shard_map`` with the mesh and specs as plain arguments.
    ``check_vma=None`` keeps jax's default (True). That default is
    load-bearing under differentiation: the transpose of a replicated
    input's cast to device-varying is the psum of its cotangent, and
    only VMA typing inserts it."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def in_named_axis(axis: str) -> bool:
    """True when ``axis`` is bound (we are tracing inside a shard_map /
    pmap body mapped over it). Probes with ``axis_index``, which raises
    NameError for an unbound axis."""
    try:
        jax.lax.axis_index(axis)
        return True
    except NameError:
        return False
