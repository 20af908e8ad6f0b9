"""`generation_pool.Driver` for the EXPERT decoder family, given by its SOURCE's
keys.

`drivers/generation_pool_source.py` builds a `LoopedDecoderConfig` and may not
be edited; this one stands beside it for a configuration whose file holds the
published `config.json` of a model with routed experts, window and full
attention layers and grouped key-value heads (`configs/k_exaone_236b.json`),
cut to one chip's share. It overrides two methods:

- `setup`: the engine's config is built BY THE PROGRAM from the source's keys
  (`ExpertDecoderConfig.from_source`: the file's `num_experts` is what this chip
  holds, `num_experts_published` the router's width), with the deployment's
  context cap (`engine.max_context`) and the experts held (`experts_held`:
  `first`, `count`); the engine also gets the KV pool's dtype
  (`engine.kv_dtype`). The family's module is imported here, where it is built,
  not by `import paddle_tpu.generation`. The weights come from the reference
  module (bfloat16, stacked by layer; the SAME arrays when `compare` asks
  again). The clients' start and the wait for the warm-up completions are the
  parent's (`_start_clients`).
- `_counters`: adds what the new per-layer readers need, each as the program
  counts it a mixed step: `moe_pairs` (`STAT_generation_moe_pairs`: token-expert
  pairs computed here), `moe_experts_touched` (`..._moe_experts_touched`:
  layer-experts with at least one token), `moe_peak_load` (`..._moe_peak_load`:
  the largest load of a layer, summed over layers), and `attended_tokens`
  (`STAT_generation_attended_tokens`: summed over the layers for this family, a
  window layer at most its window a slot). `window()` adds `experts_held` and
  `sparse_layers` from the program's config, for the mean load.

Everything else (the clients, the window and its token count, the drain, the
sample, `compare`) is inherited.
"""
import time

from benchmark import harness
from benchmark.drivers import generation_pool

STATS = {"moe_pairs": "STAT_generation_moe_pairs",
         "moe_experts_touched": "STAT_generation_moe_experts_touched",
         "moe_peak_load": "STAT_generation_moe_peak_load",
         "attended_tokens": "STAT_generation_attended_tokens"}


class Driver(generation_pool.Driver):
    def setup(self):
        from paddle_tpu.generation import GenerationEngine, GenerationPool
        from paddle_tpu.generation.moe_window import ExpertDecoderConfig
        from paddle_tpu.flags import get_flag
        cfg = self.cfg
        eng = cfg["engine"]
        self._draw_requests()
        held = cfg["experts_held"]
        dcfg = ExpertDecoderConfig.from_source(
            cfg, eng["max_context"], (held["first"], held["count"]))
        if self.engine is not None:
            # an engine handed over by calibrate.py still holds the last
            # seed's weights: two sets do not fit beside its pools
            self.engine.params = None
        weights = self.ref.make_weights(cfg, self.seed)
        block = int(get_flag("FLAGS_generation_block_size"))
        t0 = time.perf_counter()
        if self.engine is None:
            self.engine = GenerationEngine(
                dcfg, weights, decode_width=eng["decode_width"],
                num_blocks=eng["kv_pool_tokens"] // block,
                kv_dtype=eng["kv_dtype"])
            self.engine.warmup()
        else:
            self.engine.params = weights
        del weights
        e = self.engine
        harness.say("engine warm-up %.1fs; token_budget %d, prefill_chunk %d, "
                    "block_size %d, kernel %s; %d KV layers of %d (windows "
                    "%s), experts %d..%d of %d, context cap %d, pools %.2f GB "
                    "%s, lookahead %d"
                    % (time.perf_counter() - t0, e.token_budget,
                       e.prefill_chunk, e.kv.block_size, e.kernel,
                       dcfg.kv_layers, dcfg.kv_row, list(dcfg.kv_windows),
                       dcfg.experts_first,
                       dcfg.experts_first + dcfg.experts_held - 1,
                       dcfg.num_experts, dcfg.max_seq_len,
                       e.kv_pool_bytes() / 1e9, e.kv_dtype, e.lookahead))
        self.pool = GenerationPool(self.engine)
        self._start_clients()

    def _counters(self):
        from paddle_tpu.monitor import stat_get
        return dict(super()._counters(),
                    **{k: stat_get(s) for k, s in STATS.items()})

    def window(self, seconds):
        out = super().window(seconds)
        dcfg = self.engine.cfg
        out["counters"].update(experts_held=dcfg.experts_held,
                               sparse_layers=dcfg.sparse_layers)
        return out
