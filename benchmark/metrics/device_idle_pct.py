"""Share of the traced slice in which no operation ran on the device: 1 - the
union of the device-op intervals over the slice (benchmark/trace_reduce.py)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
