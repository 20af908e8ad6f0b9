"""Device time of the optimizer, a train step: self time under the
program's scope `optimizer` (`jit.TrainStep`: the whole update, gradient
clipping and AMP's master-weight casts with it), over the executions of the
step's program in the traced slice."""
from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.scope_ms_a_step(
        ctx, ("optimizer",), trace_scopes.STEP_MODULE[ctx["kind"]])
