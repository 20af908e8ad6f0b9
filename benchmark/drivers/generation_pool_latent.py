"""`generation_pool_expert.Driver` for the LATENT-attention expert family, given
by its SOURCE's keys.

A configuration whose file holds the published `config.json` of a model with
multi-head latent attention and routed experts (`configs/kimi_k2_6.json`), cut
to one chip's share. It overrides `setup` alone:

- the family's module is imported FIRST, before a request is drawn or a weight
  made: a program without it (an older commit with these files laid over it)
  fails in seconds;
- the engine's config is built BY THE PROGRAM from the source's keys
  (`LatentDecoderConfig.from_source`: the file's `n_routed_experts` is what this
  chip holds, `n_routed_experts_published` the router's width), with the
  deployment's context cap (`engine.max_context`) and the experts held
  (`experts_held`: `first`, `count`);
- the engine gets what the deployment fixes: lanes, the pool's positions and
  dtype, the chunked-prefill size and the slots a step (`engine.prefill_chunk`,
  `engine.token_budget`).

`_counters` gives, beside the inherited routing counts, `context_rows`
(`STAT_generation_context_rows`: each lane's context once a step, summed over
the layers) and `attended_slots` (`STAT_generation_attended_tokens`: every
slot's context, summed over the layers): `metrics/latent_attn_roofline_pct.py`
takes the rows a step must read from the first and the products it must make
from the second. `attended_tokens`, which `metrics/step_hbm_roofline_pct.py`
hands the reference's `step_bytes` as the positions whose rows a step reads
("nothing a kernel re-reads"), is the ROW count here: the per-slot count would
read a prompt chunk of 256 tokens' rows 256 times. The clients, the window, the
drain, the sample and `compare` are inherited.
"""
import time

from benchmark import harness
from benchmark.drivers import generation_pool_expert


class Driver(generation_pool_expert.Driver):
    def setup(self):
        from paddle_tpu.generation.mla_moe import LatentDecoderConfig
        from paddle_tpu.generation import GenerationEngine, GenerationPool
        from paddle_tpu.flags import get_flag
        cfg = self.cfg
        eng = cfg["engine"]
        self._draw_requests()
        held = cfg["experts_held"]
        dcfg = LatentDecoderConfig.from_source(
            cfg, eng["max_context"], (held["first"], held["count"]))
        if self.engine is not None:
            # an engine handed over by calibrate.py still holds the last
            # seed's weights: two sets do not fit beside its pool
            self.engine.params = None
        weights = self.ref.make_weights(cfg, self.seed)
        block = int(get_flag("FLAGS_generation_block_size"))
        t0 = time.perf_counter()
        if self.engine is None:
            self.engine = GenerationEngine(
                dcfg, weights, decode_width=eng["decode_width"],
                num_blocks=eng["kv_pool_tokens"] // block,
                kv_dtype=eng["kv_dtype"],
                prefill_chunk=eng["prefill_chunk"],
                token_budget=eng["token_budget"])
            self.engine.warmup()
        else:
            self.engine.params = weights
        del weights
        e = self.engine
        harness.say("engine warm-up %.1fs; token_budget %d, prefill_chunk %d, "
                    "block_size %d, kernel %s; %d latent layers of rows %d, "
                    "experts %d..%d of %d, context cap %d, pool %.2f GB %s, "
                    "lookahead %d"
                    % (time.perf_counter() - t0, e.token_budget,
                       e.prefill_chunk, e.kv.block_size, e.kernel,
                       dcfg.kv_layers, dcfg.kv_row, dcfg.experts_first,
                       dcfg.experts_first + dcfg.experts_held - 1,
                       dcfg.num_experts, dcfg.max_seq_len,
                       e.kv_pool_bytes() / 1e9, e.kv_dtype, e.lookahead))
        self.pool = GenerationPool(self.engine)
        self._start_clients()

    def _counters(self):
        from paddle_tpu.monitor import stat_get
        rows = stat_get("STAT_generation_context_rows")
        return dict(super()._counters(), context_rows=rows,
                    attended_tokens=rows, attended_slots=stat_get(
                        "STAT_generation_attended_tokens"))
