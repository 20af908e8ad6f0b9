"""The flash-attention kernels' share of their roofline in the traced slice.

Time: the device durations of the flash kernels' events. A Mosaic kernel
carries no name of its own into the trace (the HLO instruction is called
after the jvp/transpose it came from), so the events are told by shape: a
custom call whose HLO text holds the attention operand `[B,heads,S,d]` of this
cell. Forward calls are those that also put out the log-sum-exp rows
`[B,heads,S,1]`; each forward call stands for one layer of one step, forward
and backward. Work: the operations and bytes the algorithm needs for that,
from shapes (benchmark/flops.py). The least time the chip could take is the
larger of operations over peak and bytes over bandwidth, forward and backward
each; `notes` says which bound.
"""
from benchmark import flops, trace_reduce


def read(ctx):
    cfg, wl, peak = ctx["config"], ctx["workload"], ctx["peak"]
    if ctx["planes"] is None or peak is None or wl["kind"] != "train":
        return None
    B, S = wl["batch"], wl["seq_len"]
    nh = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // nh
    operand = "[%d,%d,%d,%d]" % (B, nh, S, hd)
    lse = "f32[%d,%d,%d,1]" % (B, nh, S)

    def text(name, st):
        return " ".join([name] + [str(v) for v in st.values()])

    def is_flash(name, st):
        t = text(name, st)
        return "custom-call" in t and operand in t
    lo_hi = trace_reduce.window_of(ctx["planes"])
    evs = trace_reduce.events_matching(ctx["planes"], is_flash)
    if lo_hi:
        evs = [e for e in evs if e[1] >= lo_hi[0] and e[1] + e[2] <= lo_hi[1]]
    fwd = [e for e in evs if lse in text(e[0], e[3]).split(" custom-call")[0]]
    if not evs or not fwd:
        return None
    itemsize = 2 if cfg.get("amp_dtype") == "bfloat16" else 4
    cost = flops.flash_attention_cost(B, nh, S, hd, itemsize)
    t_f, b_f = flops.roofline_seconds(*cost["fwd"], peak)
    t_b, b_b = flops.roofline_seconds(*cost["bwd"], peak)
    spent = sum(e[2] for e in evs) / 1e9
    ctx["notes"]["flash_attn_roofline"] = {
        "events": len(evs), "forward_events": len(fwd), "kernel_s": spent,
        "bound_fwd": b_f, "bound_bwd": b_b}
    return 100.0 * len(fwd) * (t_f + t_b) / spent
