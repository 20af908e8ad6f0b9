"""Mean time of the engine's mixed step in the window, from the engine's own
timer (TIMER_generation_mixed_step_us: sum over count, as grown in the
window). Host time around the compiled call and the fetch of its tokens."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    return c["step_us"] / c["steps"] / 1e3
