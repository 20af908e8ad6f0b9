"""The whole step's share of the chip's bf16 peak, the needed operations
counted by the CONFIGURATION'S OWN reference module: `request_flops(cfg,
prompt_len, new_tokens)` of `references/<reference>.py`, summed over the
requests that ended in the window, over window x chips x peak
(benchmark/peaks.json). `mfu_pct` counts a GPT-2 layer from GPT-2's key names
(benchmark/flops.py); this one reads whatever model the configuration names.
The reference module is the one the harness loaded for this run
(`harness.Files.load_module`, under `benchmark_references_<name>`). None where
there is no peak, no finished request or no such count."""
import sys


def reference_of(ctx):
    return sys.modules.get("benchmark_references_%s"
                           % ctx["config"].get("reference"))


def read(ctx):
    c, ref = ctx["counters"], reference_of(ctx)
    if ctx["peak"] is None or not ctx["window_s"] or not c.get("finished") \
            or not hasattr(ref, "request_flops"):
        return None
    need = sum(ref.request_flops(ctx["config"], p, n)
               for p, n in c["finished"])
    return 100.0 * need / (ctx["window_s"] * ctx["cell"]["chips"]
                           * ctx["peak"]["bf16_flops_per_s"])
