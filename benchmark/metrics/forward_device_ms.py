"""Device time of the forward pass, a train step: self time under the
program's scope `forward` (`jit.TrainStep`: model and loss) and not under
jax's `transpose(`, over the executions of the step's program in the traced
slice."""
from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.scope_ms_a_step(
        ctx, ("forward",), trace_scopes.STEP_MODULE[ctx["kind"]],
        backward=False)
