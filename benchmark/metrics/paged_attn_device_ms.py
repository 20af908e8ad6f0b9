"""Device time of the paged attention and of the KV pools' update, a step
of the engine: self time under the program's scopes `paged_attention` (the
gather and the attention over it, in the reference form as in the Pallas
form: `kernels/paged_attention.py`) and `kv_write` (`generation/model.py`),
over the executions of the mixed step's program in the traced slice."""
from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.scope_ms_a_step(ctx, ("paged_attention", "kv_write"),
                                        trace_scopes.STEP_MODULE[ctx["kind"]])
