"""The BERT reference at the toy sizes of the CPU rehearsal, with limits of
its own: float32 on both sides and dropout off, so the program and the
reference agree to rounding at this size (1e-7 losses and head gaps, 2e-5
update norms read on the CPU), and the limits sit a little above that. The
real cell's limits, read on the chip, are in
`benchmark/references/bert_base_mlm.py`."""
from benchmark.references.bert_base_mlm import (  # noqa: F401
    CONTROLS, FAULTS, Reference, compare, make_weights)

LIMITS = {"head_bias_grad_gap": 1e-3, "head_grad_gap": 1e-3,
          "encoder_grad_shortfall": 1e-3, "median_update_gap": 1e-3}
