"""Share of the mixed step's token slots that carried a token: 1 - padded
slots (STAT_generation_pad_tokens) over steps x the engine's token_budget,
both as grown in the window."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps") or not c.get("token_budget"):
        return None
    return 100.0 * (1.0 - c["pad_tokens"] / (c["steps"] * c["token_budget"]))
