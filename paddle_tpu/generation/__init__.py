"""Autoregressive generation engine (docs/generation.md).

Three pillars on top of the serving stack:

- paged KV cache: `KVCacheManager` ledgers a fixed preallocated block
  pool (`FLAGS_generation_kv_blocks` x `FLAGS_generation_block_size`
  tokens per layer); sequences hold block tables, not buffers.
- decode engine: `GenerationEngine` — chunked prefill and
  single-token decode in ONE mixed step over the pool
  (kernels/paged_attention.py), greedy/top-k/top-p samplers with
  per-sequence PRNG. Fixed shapes end to end: steady state replays
  that one compiled step with zero recompiles.
- model families: `DecoderConfig` (the GPT block, model.py) and
  `LoopedDecoderConfig` (a stack run several times a token, looped.py)
  reach the engine through the same seam on the config object.
- continuous batching: `GenerationPool` admits requests into the
  in-flight decode batch every step (join at prefill, leave at
  EOS/max-len), `ServingQueueFull` backpressure, per-sequence error
  isolation.
"""
from .engine import (GenerationEngine, GenerationRequest,
                     GenerationResult, NaiveGenerator)
from .kv_cache import TRASH_BLOCK, BlockPoolExhausted, KVCacheManager
from .looped import LoopedDecoderConfig
from .model import DecoderConfig, forward_full, forward_paged, init_params
from .sampling import SamplingParams, sample_tokens
from .scheduler import GenerationPool

__all__ = [
    "BlockPoolExhausted", "DecoderConfig", "GenerationEngine",
    "GenerationPool", "GenerationRequest", "GenerationResult",
    "KVCacheManager", "LoopedDecoderConfig", "NaiveGenerator",
    "SamplingParams", "TRASH_BLOCK",
    "forward_full", "forward_paged", "init_params", "sample_tokens",
]
