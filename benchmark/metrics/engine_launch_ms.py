"""The launch of the engine's compiled step: `pt/engine/dispatch` less the
runtime's transfers inside it (`engine_upload_ms`), over the count of
dispatches, in the traced slice (`benchmark/engine_trace.py`). None where the
trace holds no dispatch with a transfer in it."""
from benchmark import engine_trace, trace_scopes

DISPATCH = "pt/engine/dispatch"


def read(ctx):
    host = trace_scopes.host(ctx)
    got = engine_trace.pieces(ctx)
    n = host["count"].get(DISPATCH) if host else None
    inside = [p for p in got if DISPATCH in p[2]]
    up = engine_trace.seconds(inside, engine_trace.is_upload)
    if not n or not up:
        return None
    return 1e3 * (engine_trace.seconds(inside, bool) - up) / n
