"""Executables jax built or fetched inside the measured window (jax.monitoring
backend_compile_duration events): should be 0, every shape being warmed in
set-up."""


def read(ctx):
    return ctx["window_compiles"]
