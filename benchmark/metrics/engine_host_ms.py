"""Host time of the engine a step, outside the compiled call and the fetch
of its tokens: the `pt/engine/step` span less its `pt/engine/dispatch` and
`pt/engine/fetch` (that is: the step's self time and every other child of it:
admit, plan, emit), plus the `pt/pool/*` spans between steps (the wait for the
pool's lock, the admit under it, the delivery of finished futures), in the
traced slice. Each kind of span is averaged over its own count: a step in
flight when the profiler's session opens or closes is not in the trace, though
its children are, so the sums of parents and children do not match at the
slice's edges. `telemetry.span` opens them in `generation/engine.py` and
`generation/scheduler.py`. None where the trace holds no such span (the parent
of PR 27)."""
from benchmark import trace_scopes
from benchmark.harness import BenchError

STEP = "pt/engine/step"
DEVICE_SIDE = ("pt/engine/dispatch", "pt/engine/fetch")
MARKS = ("pt/engine/", "pt/pool/")


def read(ctx):
    red = trace_scopes.host(ctx)
    if red is None or not red["count"].get(STEP):
        return None
    missing = [n for n in DEVICE_SIDE if not red["count"].get(n)]
    if missing:
        raise BenchError("the trace holds %s but no %s" % (STEP, missing))
    return 1e3 * sum(s / red["count"][n] for n, s in red["self_s"].items()
                     if n.startswith(MARKS) and n not in DEVICE_SIDE)
