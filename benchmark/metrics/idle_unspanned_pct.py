"""Share of the device's idle time that no phase of the engine explains.

Every idle interval of device 0 in the traced slice, as `trace_reduce.reduce`
finds them, is split by overlap over what is open at each instant on the host
thread that holds the `pt/engine/step` spans (the pool's serving thread): the
innermost `pt/` span, or the runtime's transfer inside one
(`benchmark/engine_trace.py`). The metric is the share of that idle time under
no span or under the step's own self time: what the program cannot yet put
down to one of its phases. The whole split goes to
`notes.idle_by_engine_span` as ms a step by span. None where there is no
device plane (the CPU rehearsal) or no engine step."""
from benchmark import engine_trace, trace_reduce, trace_scopes


def read(ctx):
    planes = ctx.get("planes")
    if not planes or not trace_reduce.device_planes(planes):
        return None
    got = engine_trace.pieces(ctx)
    host = trace_scopes.host(ctx)
    steps = host["count"].get(engine_trace.STEP) if host else None
    if not got or not steps:
        return None
    by = engine_trace.split(engine_trace.idle_intervals(planes), got)
    idle = sum(by.values())
    ctx["notes"]["idle_by_engine_span"] = {
        "idle_s": idle / 1e9, "steps": steps,
        "ms_a_step": {n: ns / 1e6 / steps for n, ns in
                      sorted(by.items(), key=lambda kv: -kv[1])}}
    if idle <= 0:
        return 0.0
    return 100.0 * (by.get(engine_trace.NO_SPAN, 0.0)
                    + by.get(engine_trace.STEP, 0.0)) / idle
