"""Device time of the latent attention, a step of the engine: self time under
the program's scope `latent_attention` (kernels/latent_attention.py: the Pallas
kernel on the chip, the gather and the attention in the reference form), over
the executions of the mixed step's program in the traced slice. The query's
absorption and the value up-projection lie outside it, under `latent_absorb`
and `latent_out`; the rows' writes under `kv_write`. None where the trace names
nothing, or nothing under the scope (a program without the latent family)."""
from benchmark.metrics.moe_device_ms import scope_ms


def read(ctx):
    return scope_ms(ctx, "latent_attention")
