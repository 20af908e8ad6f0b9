"""The latent family's reference at the toy sizes of the CPU rehearsal: float32
weights on both sides, so the engine serves the reference's first choice but for
rounding, and limits of its own. The real cell's limits, read on the chip, are
in `benchmark/references/kimi_k2_6.py`."""
import functools

import jax.numpy as jnp

from benchmark.references import kimi_k2_6 as _real
from benchmark.references.kimi_k2_6 import (  # noqa: F401
    CONTROLS, Reference, compare, expert_bytes, latent_bytes, latent_flops,
    request_flops)

make_weights = functools.partial(_real.make_weights, dtype=jnp.float32)
LIMITS = {"served_logit_gap_mean": 1e-5, "served_logit_gap": 1e-4}
