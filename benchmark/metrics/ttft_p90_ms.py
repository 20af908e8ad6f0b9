"""90th percentile, over the requests that ended (or failed: beyond any
percentile) inside the window, of first token minus submit, as the program's
`RequestTrace` stamps them on the host's monotonic clock. A per-layer metric
and no end-to-end one: in a closed loop that keeps the engine saturated it is
the wait for a prompt slot, and it swings with which requests the window's
edges catch (PERF.md section 6, PR 26)."""


def read(ctx):
    return ctx["end_to_end"].get("ttft_p90_ms")
