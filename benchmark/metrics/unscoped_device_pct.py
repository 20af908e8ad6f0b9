"""Share of the device's busy time in the traced slice that lies under no
scope of the program's: operations the compiler made with no name of the
program's on them (copies at a step's edge), and programs that open no scope.
None where the trace names nothing at all (the parent of PR 27)."""
from benchmark import trace_scopes


def read(ctx):
    red = trace_scopes.device(ctx)
    if red is None or red["busy_s"] <= 0:
        return None
    return 100.0 * red["by_scope"].get(trace_scopes.UNSCOPED, 0.0) \
        / red["busy_s"]
