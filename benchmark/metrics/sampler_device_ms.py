"""Device time of the sampler, a step of the engine: self time of the
operations under the program's scope `sampler` (opened in the engine's step
builders around the gather of the sampled rows and `sample_tokens`), over the
executions of the mixed step's program in the traced slice
(benchmark/trace_scopes.py)."""
from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.scope_ms_a_step(ctx, ("sampler",),
                                        trace_scopes.STEP_MODULE[ctx["kind"]])
