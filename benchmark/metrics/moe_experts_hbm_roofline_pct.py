"""The grouped expert products' share of their memory roofline: the bytes the
experts TOUCHED in a step need from HBM over the bytes the chip could have moved
in the device time under the program's scope `moe_experts`.

Needed bytes: `expert_bytes(cfg, experts_touched)` of the configuration's
reference module: the three matrices of every layer-expert that had at least one
token, once each, by the program's counter (`STAT_generation_moe_experts_touched`,
grown in the window, over the window's steps: bytes A STEP). Only weights that
HAD to be read are counted, so the share cannot pass 100: an expert no token
chose costs nothing, and neither do the activations. Time: self time under
`moe_experts` (the gather of the sorted rows, the two grouped products, the
weighted sum back to the tokens) a step of the mixed step's program in the
traced slice. A decode step's experts are bound by the stream of their weights:
this is how near the grouped matmul comes to it. None where a counter, the
count or the scope is missing; never 0."""
from benchmark.metrics.config_mfu_pct import reference_of
from benchmark.metrics.moe_device_ms import scope_ms


def read(ctx):
    c, ref, peak = ctx["counters"], reference_of(ctx), ctx["peak"]
    if peak is None or not c.get("steps") or not c.get("moe_experts_touched") \
            or not hasattr(ref, "expert_bytes"):
        return None
    ms = scope_ms(ctx, "moe_experts")
    if ms is None:
        return None
    need = ref.expert_bytes(ctx["config"],
                            c["moe_experts_touched"]) / c["steps"]
    ctx["notes"]["moe_experts_hbm_roofline"] = {
        "bytes_a_step": need, "device_ms_a_step": ms,
        "experts_touched_a_step": c["moe_experts_touched"] / c["steps"]}
    return 100.0 * need / (ms / 1e3 * peak["hbm_bytes_per_s"])
