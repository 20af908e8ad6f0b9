"""Device time of the looped stack, a step of the engine: self time of the
operations under the program's scope `loop_pass` (`generation/looped.py`: around
one pass's layers and the norm that ends it; every pass of a step runs the one
compiled body, so this is all of them), over the executions of the mixed step's
program in the traced slice. The scopes `qkv`, `kv_write`, `paged_attention`,
`attn_out` and `mlp` lie inside it."""
from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.scope_ms_a_step(ctx, ("loop_pass",),
                                        trace_scopes.STEP_MODULE[ctx["kind"]])
