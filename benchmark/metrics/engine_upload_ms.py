"""Host-to-device transfers of the engine a step: the seconds under the
runtime's own transfer events (`DevicePut`) inside the engine's spans on its
thread, in the traced slice, over the count of `pt/engine/step`
(`benchmark/engine_trace.py`). Most lie inside `pt/engine/dispatch`: the
compiled call's own transfer of the step's two packed host arrays. The
parent's trace holds them too. None where the trace holds no engine step or
no such transfer."""
from benchmark import engine_trace, trace_scopes


def read(ctx):
    host = trace_scopes.host(ctx)
    got = engine_trace.pieces(ctx)
    steps = host["count"].get(engine_trace.STEP) if host else None
    up = engine_trace.seconds(got, engine_trace.is_upload)
    if not steps or not up:
        return None
    return 1e3 * up / steps
