"""The mixed step's share of its memory roofline: the bytes a step NEEDS from
HBM over the bytes the chip could have moved in the step's device time.

Needed bytes: `step_bytes(cfg, steps, attended_tokens)` of the configuration's
reference module (weights once a pass a step, the head once, the K and V rows
of the positions attended; nothing a kernel re-reads), from the window's
counters: `steps`, and `attended_tokens` as the program counts it
(`STAT_generation_attended_tokens`, grown in the window), so bytes A STEP of the
window. Time: the device seconds of the step's program (`XLA Modules` line,
`jit_generation_mixed*`) a run, in the traced slice. The closed loop is steady,
so the window's mean step and the slice's are the same step. A decode step is
bound by memory, not by the MXU: this is its roofline, and `config_mfu_pct` the
share of the compute peak beside it. None where the trace, the counter or the
count is missing."""
from benchmark import trace_scopes
from benchmark.metrics.config_mfu_pct import reference_of


def read(ctx):
    c, ref, peak = ctx["counters"], reference_of(ctx), ctx["peak"]
    if peak is None or not c.get("steps") or not c.get("attended_tokens") \
            or not hasattr(ref, "step_bytes"):
        return None
    red = trace_scopes.device(ctx)
    if red is None:
        return None
    mods = [m for n, m in red["modules"].items()
            if n.startswith(trace_scopes.STEP_MODULE[ctx["kind"]])]
    runs = sum(m["runs"] for m in mods)
    seconds = sum(m["seconds"] for m in mods)
    if runs <= 0 or seconds <= 0:
        return None
    need = ref.step_bytes(ctx["config"], c["steps"],
                          c["attended_tokens"]) / c["steps"]
    ctx["notes"]["step_hbm_roofline"] = {
        "bytes_a_step": need, "device_s_a_step": seconds / runs,
        "attended_tokens_a_step": c["attended_tokens"] / c["steps"]}
    return 100.0 * need / (seconds / runs * peak["hbm_bytes_per_s"])
