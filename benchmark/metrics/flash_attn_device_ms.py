"""Device time of the flash-attention kernels, a train step: self time under
the program's scope `flash_attention` (around each `pallas_call` in
`kernels/flash_attention.py`: forward, dQ, dK/dV), forward and backward, over
the executions of the step's program in the traced slice. The same events
`flash_attn_roofline` finds by operand shape: `kernel_s` of its note, over the
steps, is this number."""
from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.scope_ms_a_step(
        ctx, ("flash_attention",), trace_scopes.STEP_MODULE[ctx["kind"]])
