"""Mean duration of `pt/trainstep/call`, the host span `telemetry.span` opens
around the whole of `jit.TrainStep.__call__` (staging, the rng split, the
dispatch of the compiled step), in the traced slice. None where the trace
holds no such span (the parent of PR 27)."""
from benchmark import trace_scopes

CALL = "pt/trainstep/call"


def read(ctx):
    red = trace_scopes.host(ctx)
    if red is None or not red["count"].get(CALL):
        return None
    return 1e3 * red["total_s"][CALL] / red["count"][CALL]
