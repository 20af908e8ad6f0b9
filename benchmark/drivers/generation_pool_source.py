"""`generation_pool.Driver` for a configuration given by its SOURCE's keys.

`drivers/generation_pool.py` builds the engine's config from GPT-2's key names
(`n_embd`, `n_layer`, `mlp_ratio`) and gives the engine two numbers of the
deployment. A configuration whose file holds the published `config.json` of a
looped decoder (`hidden_size`, `num_hidden_layers`, `head_dim`,
`total_ut_steps`, ...) cannot go through that, and that file may not be edited;
this one stands beside it and overrides two methods:

- `setup`: the engine's config is built BY THE PROGRAM from the source's keys
  (`LoopedDecoderConfig.from_source`, which reads what it knows and ignores the
  rest), with the deployment's context cap (`engine.max_context`: the engine
  sizes its block tables by it, the model's rotary positions need no table);
  the engine also gets the KV pool's dtype (`engine.kv_dtype`). The weights
  come from the reference module as before (here bfloat16, stacked by layer;
  the module hands out the SAME arrays when `compare` asks again, so the
  comparison holds no second 5.34 GB set).
  The clients' start and the wait for the warm-up completions are the parent's
  (`_start_clients`).
- `_counters`: adds `attended_tokens` (`STAT_generation_attended_tokens`: per
  mixed step, the sum over live slots of the positions attended), which
  `metrics/step_hbm_roofline_pct.py` turns into the KV bytes a step needs.

Everything else (the clients, the window and its token count, the drain, the
sample, `compare`) is inherited.
"""
import time

from benchmark import harness
from benchmark.drivers import generation_pool


class Driver(generation_pool.Driver):
    def setup(self):
        from paddle_tpu.generation import GenerationEngine, GenerationPool
        from paddle_tpu.generation.looped import LoopedDecoderConfig
        from paddle_tpu.flags import get_flag
        cfg = self.cfg
        eng = cfg["engine"]
        self._draw_requests()
        dcfg = LoopedDecoderConfig.from_source(cfg, eng["max_context"])
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
                    "block_size %d, kernel %s; %d KV layers of %d, context cap "
                    "%d, pools %.2f GB %s, lookahead %d"
                    % (time.perf_counter() - t0, e.token_budget,
                       e.prefill_chunk, e.kv.block_size, e.kernel,
                       dcfg.kv_layers, dcfg.kv_row, dcfg.max_seq_len,
                       e.kv_pool_bytes() / 1e9, e.kv_dtype, e.lookahead))
        self.pool = GenerationPool(self.engine)
        self._start_clients()

    def _counters(self):
        from paddle_tpu.monitor import stat_get
        return dict(super()._counters(), attended_tokens=stat_get(
            "STAT_generation_attended_tokens"))
