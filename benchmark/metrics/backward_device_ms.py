"""Device time of the backward pass, a train step: self time under
`transpose(jvp(forward))`, jax's own wrapper around the forward's names, over
the executions of the step's program in the traced slice."""
from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.scope_ms_a_step(
        ctx, ("forward",), trace_scopes.STEP_MODULE[ctx["kind"]],
        backward=True)
