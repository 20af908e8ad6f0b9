"""Peak device memory of the run up to the window's close (the result's
`memory_peak_bytes`: buffers plus what the running program reserved, on the
fullest chip, read before the reference runs; harness.memory_peak_bytes) over
the chip's HBM (benchmark/peaks.json)."""


def read(ctx):
    if ctx["peak"] is None or not ctx["memory_peak_bytes"]:
        return None
    return 100.0 * ctx["memory_peak_bytes"] / ctx["peak"]["hbm_bytes"]
