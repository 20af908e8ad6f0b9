"""The GPT-2 reference at the toy sizes of the CPU rehearsal, with limits of
its own: float32 on both sides, so the engine serves the reference's first
choice but for rounding. The real cell's limits, read on the chip, are in
`benchmark/references/gpt2_124m.py`."""
from benchmark.references.gpt2_124m import (  # noqa: F401
    CONTROLS, Reference, compare, make_weights)

LIMITS = {"served_logit_gap": 1e-4}
