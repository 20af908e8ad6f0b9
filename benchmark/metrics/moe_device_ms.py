"""Device time of the sparse feed-forward, a step of the engine: self time of
the operations under the program's scope `moe` (`generation/moe_window.py`:
around a sparse layer's router, grouped expert products and shared expert; the
scopes `moe_router`, `moe_experts` and `moe_shared` lie inside it, and every
sparse layer of a step runs the one compiled body, so this is all of them), over
the executions of the mixed step's program in the traced slice. None where the
trace names nothing, or nothing under `moe` (a program without the scope)."""
from benchmark import trace_scopes


def scope_ms(ctx, scope):
    """Device milliseconds under `scope` a step of the mixed step's program, or
    None where the trace, the program's executions or the scope are missing."""
    red = trace_scopes.device(ctx)
    if red is None:
        return None
    n = trace_scopes.runs(red, trace_scopes.STEP_MODULE[ctx["kind"]])
    sec = trace_scopes.under(red, scope)
    if n <= 0 or sec <= 0:
        return None
    return 1e3 * sec / n


def read(ctx):
    return scope_ms(ctx, "moe")
