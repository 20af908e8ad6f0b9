"""The whole step's share of the chip's bf16 peak: operations the model NEEDS
for the work the window completed (from shapes, benchmark/flops.py; recomputed
work does not count), over window x chips x peak (benchmark/peaks.json)."""
from benchmark import flops


def read(ctx):
    c, cfg, wl = ctx["counters"], ctx["config"], ctx["workload"]
    if ctx["peak"] is None or not ctx["window_s"]:
        return None
    if wl["kind"] == "train":
        if not c.get("steps"):
            return None
        need = c["steps"] * flops.bert_train_step_flops(
            cfg, wl["batch"], wl["seq_len"], wl["masked"])
    elif wl["kind"] == "serve":
        if not c.get("finished"):
            return None
        need = sum(flops.decoder_request_flops(cfg, p, n)
                   for p, n in c["finished"])
    else:
        return None
    return 100.0 * need / (ctx["window_s"] * ctx["cell"]["chips"]
                           * ctx["peak"]["bf16_flops_per_s"])
