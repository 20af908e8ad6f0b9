"""How uneven the routing was: the largest load of a sparse layer over the mean
load of its held experts, both as the program counts them a mixed step and grown
in the window: `STAT_generation_moe_peak_load` (the most tokens any one held
expert took in a layer, summed over the layers) over `STAT_generation_moe_pairs`
(token-expert pairs computed here, summed over the layers) / experts held. 1.0
when every held expert takes the same; the grouped matmul pays for the
straggler, whose rows decide how many tiles a group spans. None where a counter
is missing or no pair was routed here."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("moe_pairs") or not c.get("moe_peak_load") \
            or not c.get("experts_held"):
        return None
    return c["moe_peak_load"] / (c["moe_pairs"] / c["experts_held"])
