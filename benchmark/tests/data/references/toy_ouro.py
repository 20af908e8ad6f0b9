"""The looped decoder's reference at the toy sizes of the CPU rehearsal:
float32 weights on both sides, so the engine serves the reference's first
choice but for rounding, and limits of its own. The real cell's limits, read on
the chip, are in `benchmark/references/ouro_2_6b.py`."""
import functools

import jax.numpy as jnp

from benchmark.references import ouro_2_6b as _real
from benchmark.references.ouro_2_6b import (  # noqa: F401
    CONTROLS, Reference, compare, request_flops, step_bytes)

make_weights = functools.partial(_real.make_weights, dtype=jnp.float32)
LIMITS = {"served_logit_gap": 1e-4}
