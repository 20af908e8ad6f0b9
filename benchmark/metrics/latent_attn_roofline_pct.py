"""The latent attention's share of its roofline: the time the step's slots NEED
on the chip over the device time the kernel took.

Need, a step: the longer of `latent_bytes(cfg, rows)` over the chip's HBM
bandwidth and `latent_flops(cfg, attended)` over its bf16 peak, from the
configuration's reference module and two of the program's counters, grown in
the window, over the window's steps. `rows` (`STAT_generation_context_rows`,
summed over the layers) counts each lane's context ONCE a step, however many of
its slots attend it: a prefill chunk of 256 prompt tokens reads its lane's rows
once, not 256 times. One row of the latent and the rotary key a position a
layer; not the lanes that pad it, not the rest of a block a context ends in.
`attended` (`attended_slots`: `STAT_generation_attended_tokens`, summed over
the layers) counts every slot's context: each slot's 64 heads must score and
sum every row it sees, the absorbed form's products. Either floor is one no kernel that reads a
lane's rows apart from another lane's can go under, so the share cannot pass
100 (rows two lanes share through the prefix cache count for each; the cell's
prompts share none). Time: self time under `latent_attention` a step of the
mixed step's program in the traced slice. None where a counter, the count, the
peak or the scope is missing; never 0."""
from benchmark.metrics.config_mfu_pct import reference_of
from benchmark.metrics.moe_device_ms import scope_ms


def read(ctx):
    c, ref, peak = ctx["counters"], reference_of(ctx), ctx["peak"]
    if peak is None or not c.get("steps") or not c.get("attended_slots") \
            or not c.get("context_rows") or not hasattr(ref, "latent_bytes"):
        return None
    ms = scope_ms(ctx, "latent_attention")
    if ms is None:
        return None
    attended = c["attended_slots"] / c["steps"]
    rows = c["context_rows"] / c["steps"]
    bytes_s = ref.latent_bytes(ctx["config"], rows) / peak["hbm_bytes_per_s"]
    flops_s = ref.latent_flops(ctx["config"], attended) \
        / peak["bf16_flops_per_s"]
    need_s = max(bytes_s, flops_s)
    ctx["notes"]["latent_attn_roofline"] = {
        "rows_a_step": rows, "attended_a_step": attended,
        "bytes_ms_a_step": 1e3 * bytes_s, "flops_ms_a_step": 1e3 * flops_s,
        "need_ms_a_step": 1e3 * need_s, "device_ms_a_step": ms}
    return 100.0 * need_s / (ms / 1e3)
