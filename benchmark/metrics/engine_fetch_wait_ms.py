"""The engine's wait for the device a step: the seconds under `pt/device/wait`
(the `block_until_ready` of the fetched step's result, inside
`pt/engine/fetch`) in the traced slice over the count of `pt/engine/step`.
What is left of `fetch` is the copy to the host. None where the trace holds no
such span (the parent of PR 40)."""
from benchmark import trace_scopes

STEP = "pt/engine/step"
WAIT = "pt/device/wait"


def read(ctx):
    red = trace_scopes.host(ctx)
    if red is None or not red["count"].get(STEP) \
            or not red["count"].get(WAIT):
        return None
    return 1e3 * red["total_s"][WAIT] / red["count"][STEP]
