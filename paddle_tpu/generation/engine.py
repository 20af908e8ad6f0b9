"""GenerationEngine: chunked prefill + fixed-shape mixed decode.

The engine owns the device state (params, the per-layer K/V block
pools) and a fixed-width decode batch of `decode_width` LANES. Every
step runs ONE compiled mixed executable over a fixed
`token_budget`-slot batch: each decode lane contributes one slot (its
next token), each prefilling lane contributes up to `prefill_chunk`
slots (consecutive prompt tokens at their true positions, sharing the
lane's block table), and leftover slots spin on the trash block. A
sequence's life: admitted -> blocks allocated (whole prompt + first
decode, all-or-nothing) -> parked in a free lane -> its prompt streams
through the mixed step chunk by chunk WHILE other lanes keep decoding
(no head-of-line blocking) -> the final chunk's logits sample the
first token -> decode one token per step -> leaves at
EOS/max_new_tokens, blocks freed, lane reusable.

This is the only engine: `prefill_chunk`
(FLAGS_generation_prefill_chunk) is a geometry value of at least 1.
The step loop comes in two forms over the same compiled step:
`_mixed_ahead` dispatches step n before it fetches step n-1's tokens
(the default without speculation), `_mixed_once` waits for every
step's tokens (speculation, and the tests' `lookahead=0`).

MODEL FAMILIES (PR 29). The engine reaches a model through its config
object alone: `cfg.forward_paged` (and `cfg.forward_full`, which only
the reference `NaiveGenerator` runs), the cache's
geometry (`cfg.kv_layers` cache layers of rows `cfg.kv_row`, read in
one place, `_pool_specs`), `cfg.max_seq_len` (the context cap the
block tables are sized by), `cfg.meta()` for the fingerprints. The GPT
block (`model.DecoderConfig`) and the looped decoder
(`looped.LoopedDecoderConfig`: a stack run several times a token, a
cache layer for every pass) are served by the same programs below;
nothing here names a family (docs/generation.md, "Model families").
A family may report numbers beside a step's tokens (`cfg.step_stats_len`
int32 after the sample rows of the step's one result, handed to
`cfg.record_step_stats` at the fetch: the expert family's routing
counts, `generation/moe_window.py`) and give a window a cache layer
(`cfg.kv_windows`: what the attended counters count by).
`kv_dtype="bf16"` keeps a plain bfloat16 pool; weights are served in
the dtype they arrive in.

Fixed shapes everywhere mean the steady state replays exactly the warm
executables: STAT_generation_compile counts engine-level compilations
(tests pin it at zero across a mixed-length continuous stream), and
when the persistent program cache (PR 1) is enabled the mixed and
copy-on-write steps are exported through program_cache.exported_entry
so even a fresh process skips retrace+recompile.

PR 14 layers two latency features over the mixed step, both keeping a
request's token stream what it is without them:

- PREFIX CACHE (FLAGS_generation_prefix_cache):
  admission asks the PrefixCache (kv_cache.py) for the longest cached
  chain of whole blocks matching the new prompt and attaches those
  immutable blocks read-only (refcounted) — prefill starts at the first
  uncached block, so a shared-prefix fleet pays prefill once and TTFT
  collapses to ~one chunk. As a prompt streams in, every completed
  block boundary is published back to the cache (a partial block never
  is: nothing shared is written again). K/V at a position
  is a function of the tokens at or before it, so a cached block holds
  what a cold recompute would write — tests/test_generation_prefix.py
  holds hit streams to cold streams. The one write into a still-shared
  block — the re-run of the last token of a fully cached prompt whose
  length is a block multiple — goes through COPY-ON-WRITE first: the
  ledger swaps in a private block and a one-block compiled copy clones
  the pool rows.

- SPECULATIVE DECODING (FLAGS_generation_spec_tokens = k > 0): a cheap
  drafter — "ngram" prompt-lookup (host-side, default) or a small
  "model" draft with its own paged pools — proposes up to k tokens per
  decode lane; the SAME mixed executable verifies them in one pass (a
  decode lane with q_len = k+1 is already a legal ragged row: slots at
  positions ctx..ctx+k feeding [last_token, d1..dk]). Slot j's logits
  are conditioned on the drafts before it, so its sample — taken with
  the lane's own fold_in(seed, token_index) key — is the EXACT token
  plain decode would produce iff every earlier draft matched; the
  host emits tokens until the first mismatch. Rejected drafts need no
  rollback: their K/V writes sit beyond the accepted frontier, where
  no mask exposes them before the next step's feed overwrites them.
  A draft fault degrades to plain decode (streams unchanged).

Pool pressure: if a mid-decode block extension finds the pool empty,
cold prefix-cache entries are evicted LRU-first; if the cache is dry
the YOUNGEST sequence is preempted — blocks freed (only its private
ones: shared blocks survive via their other references), request
re-queued by the scheduler — and because sampling is deterministic per
(seed, step) its replay regenerates the identical prefix
(sampling.py). A preempted producer can even re-admit THROUGH its own
published prefixes.

Instruments (track="generation"): STAT_generation_requests /
_tokens / _prefills / _evictions / _compile / _errors,
STAT_generation_prefix_{hits,misses,hit_tokens,cow_copies} /
_spec_{proposed,accepted} / _draft_faults,
GAUGE_generation_active_seqs (+ kv_cache block + prefix gauges),
TIMER_generation_mixed_step_us / _prefix_admit_us.

Request tracing (tracing.py, docs/observability.md): every request
carries a RequestTrace (opened by GenerationPool.submit, or by
engine.submit for bare-engine use) staged submit → admit →
prefill_start → first_token → done. token() observes TTFT on the first
token and TPOT deltas after — preemption replays re-observe TPOT (the
client really waits through the replay) but TTFT only once — and
preempt/replay land as trace events, so /tracez shows exactly which
requests paid for pool pressure.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import quant as _quant
from .. import telemetry as _tm
from .. import tracing as _tr
from ..core import program_cache
from ..failpoints import failpoint
from .. import flags as _flags
from ..flags import get_flag
from ..kernels.paged_attention import (kernel_form as _kernel_form,
                                       resolved_form as _resolved_form)
from ..inference import bucket_for, parse_bucket_ladder
from ..monitor import gauge_set, stat_add, timer_observe
from .kv_cache import (TRASH_BLOCK, BlockPoolExhausted, KVCacheManager,
                       PrefixCache)
from .model import DecoderConfig
from .sampling import SamplingParams, sample_tokens

__all__ = ["GenerationEngine", "GenerationRequest", "GenerationResult",
           "NaiveGenerator"]

# consecutive transient re-admission failures a REPLAYED (preempted)
# request survives before the per-request kill — see _admit()
_REPLAY_ADMIT_RETRIES = 8


@dataclass
class GenerationRequest:
    """One decoding job: prompt token ids + termination + sampling.
    `trace` is the request's RequestTrace (tracing.py) — stamped by
    GenerationPool.submit, or opened by engine.submit when absent;
    callers never set it by hand."""
    prompt: Sequence[int]
    max_new_tokens: int = 16
    eos_token: Optional[int] = None
    sampling: SamplingParams = field(default_factory=SamplingParams)
    request_id: Any = None
    trace: Any = field(default=None, repr=False, compare=False)


@dataclass
class GenerationResult:
    request_id: Any
    prompt_len: int
    tokens: List[int]              # generated ids (no prompt, no EOS)
    finish_reason: str             # "eos" | "length"
    evictions: int = 0             # times this request was replayed


class _Seq:
    """Host-side state of one in-flight sequence."""

    __slots__ = ("req", "ctx", "generated", "lane", "admit_order",
                 "evictions", "prefilled", "admit_failures", "pkeys",
                 "published", "pending")

    def __init__(self, req: GenerationRequest, admit_order: int):
        self.req = req
        self.ctx = 0               # tokens currently in the KV pool
        self.generated: List[int] = []
        self.lane = -1
        self.admit_order = admit_order
        self.evictions = 0
        self.prefilled = 0         # prompt tokens already in the pool
        self.admit_failures = 0    # consecutive transient re-admit fails
        self.pkeys = None          # [(boundary, hash)] — PrefixCache keys
        self.published = 0         # prompt tokens already cached
        self.pending = 0           # tokens sampled on the device and
        #                            not fetched yet (lookahead: 0 or 1)


class GenerationEngine:
    """Continuous-batching decode engine over the paged KV cache.

    `submit()` admits a request (prefill happens on the next `step()`),
    `step()` advances every active lane one token and returns the
    requests that finished, `generate()` is the batteries-included
    run-to-completion loop. The engine is NOT thread-safe — the
    scheduler (generation.GenerationPool) is the concurrent front-end.
    """

    def __init__(self, cfg: DecoderConfig, params: Dict[str, Any], *,
                 num_blocks: Optional[int] = None,
                 block_size: Optional[int] = None,
                 decode_width: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 spec_tokens: Optional[int] = None,
                 draft: Optional[str] = None,
                 draft_cfg: Optional[DecoderConfig] = None,
                 draft_params: Optional[Dict[str, Any]] = None,
                 program_cache_dir: Optional[str] = None,
                 quant_mode: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 kernel: Optional[str] = None,
                 autotune: Optional[bool] = None,
                 lookahead: Optional[int] = None):
        self.cfg = cfg
        self.params = jax.tree.map(jnp.asarray, params)
        nb = int(num_blocks if num_blocks is not None
                 else get_flag("FLAGS_generation_kv_blocks"))
        self.decode_width = int(
            decode_width if decode_width is not None
            else get_flag("FLAGS_generation_decode_width"))
        if self.decode_width < 1:
            raise ValueError("decode_width must be >= 1")
        self.spec_tokens = int(
            spec_tokens if spec_tokens is not None
            else get_flag("FLAGS_generation_spec_tokens"))
        if self.spec_tokens < 0:
            raise ValueError("spec_tokens must be >= 0")
        # drafter KIND resolves early: it is part of the autotune
        # policy key below; the model-draft arg validation stays with
        # the draft pool setup further down
        self.draft_kind = str(draft if draft is not None
                              else get_flag("FLAGS_generation_draft"))
        # quantized serving (ISSUE 15, paddle_tpu/quant): weight quant
        # mode + KV pool dtype. Both ride every program fingerprint
        # (lowering flags + the v=4 meta below) so an fp32 cached
        # program can never serve a quantized checkpoint.
        self.quant_mode = str(quant_mode if quant_mode is not None
                              else get_flag("FLAGS_quant_mode"))
        if self.quant_mode not in _quant.MODES:
            raise ValueError("unknown quant_mode %r (off|int8|fp8)"
                             % self.quant_mode)
        if self.quant_mode == "fp8" and not _quant.supports_fp8():
            raise ValueError(
                "quant_mode='fp8' needs float8_e4m3fn in this jax "
                "build/backend (quant.supports_fp8()) — use 'int8'")
        if self.quant_mode != "off" and not cfg.weight_quant:
            raise ValueError(
                "quant_mode=%r: post-training weight quantization does "
                "not know the leaves of %s" % (self.quant_mode,
                                               type(cfg).__name__))
        kvq = str(kv_dtype if kv_dtype is not None
                  else get_flag("FLAGS_generation_kv_quant"))
        if kvq == "auto":
            # follow the weight mode: a quantized deployment wants the
            # HBM saving on the pools too; fp8 KV stays opt-in
            kvq = "int8" if self.quant_mode != "off" else "fp32"
        if kvq not in _quant.KV_DTYPES:
            raise ValueError(
                "unknown kv_dtype %r (auto|fp32|bf16|int8|fp8)" % kvq)
        if kvq == "fp8" and not _quant.supports_fp8():
            raise ValueError(
                "kv_dtype='fp8' needs float8_e4m3fn in this jax "
                "build/backend (quant.supports_fp8()) — use 'int8'")
        self.kv_dtype = kvq
        # the pools the family's step takes, in its argument order: a
        # K and a V pool, or what the family declares (the latent
        # family's one pool of rows, generation/mla_moe.py)
        self._kv_names = tuple(getattr(cfg, "kv_pools",
                                       ("k_pools", "v_pools")))
        if kvq not in _PLAIN_KV and self._kv_names != ("k_pools",
                                                       "v_pools"):
            raise ValueError("kv_dtype %r: a quantized pool needs a K and "
                             "a V pool, %s declares %s" % (
                                 kvq, type(cfg).__name__, self._kv_names))
        if self.quant_mode != "off" and not _quant.is_quantized(
                self.params):
            # fp32 params are converted in-process (tests/bench
            # convenience); pre-converted checkpoints (quant.convert
            # CLI / load_quantized) pass through untouched
            self.params = jax.tree.map(
                jnp.asarray,
                _quant.quantize_decoder_params(self.params,
                                               self.quant_mode))
        self._program_cache_dir = program_cache_dir
        # --- adaptive dispatch (ISSUE 16, paddle_tpu/autotune.py) ---
        # Resolution per geometry knob: ctor arg / explicitly-set flag
        # PINS it > the persisted/tuned policy entry > flag default.
        # Tuning (trial engines over a probe workload) runs here, once
        # per (shape-bucket, backend, quant-mode) key — trial engines
        # recurse with autotune=False.
        self.autotune = bool(autotune if autotune is not None
                             else get_flag("FLAGS_autotune"))
        pins: Dict[str, Any] = {}

        def _pin(name, arg, flag, cast):
            if arg is not None:
                pins[name] = cast(arg)
            elif _flags.explicitly_set(flag):
                pins[name] = cast(get_flag(flag))
        if kernel is not None:      # no flag: `kernel=` alone pins it
            pins["kernel"] = str(kernel)
        _pin("block_size", block_size,
             "FLAGS_generation_block_size", int)
        _pin("prefill_chunk", prefill_chunk,
             "FLAGS_generation_prefill_chunk", int)
        _pin("token_budget", token_budget,
             "FLAGS_generation_token_budget", int)
        self._policy_entry = None
        if self.autotune and len(pins) < 4:
            from .. import autotune as _at
            self._policy_entry = _at.resolve_generation(
                cfg, self.params, num_blocks=nb,
                decode_width=self.decode_width,
                spec_tokens=self.spec_tokens,
                quant_mode=self.quant_mode, kv_dtype=self.kv_dtype,
                draft_kind=self.draft_kind, draft_cfg=draft_cfg,
                draft_params=draft_params, prefix_cache=prefix_cache,
                program_cache_dir=program_cache_dir, pins=pins)

        def _knob(name, flag, cast):
            if name in pins:
                return pins[name]
            if self._policy_entry is not None:
                return cast(self._policy_entry[name])
            return cast(get_flag(flag))
        # the form the steps will be traced in: a pin, the policy's
        # winner, else what the backend resolves (the Pallas kernel on
        # a TPU, the reference form elsewhere, an enclosing
        # kernel_form block before either)
        self.kernel = (pins["kernel"] if "kernel" in pins
                       else str(self._policy_entry["kernel"])
                       if self._policy_entry is not None
                       else _resolved_form())
        if self.kernel not in ("reference", "pallas"):
            raise ValueError("unknown paged-attention kernel %r "
                             "(reference|pallas)" % self.kernel)
        bs = _knob("block_size", "FLAGS_generation_block_size", int)
        self.prefill_chunk = _knob(
            "prefill_chunk", "FLAGS_generation_prefill_chunk", int)
        if self.prefill_chunk < 1:
            raise ValueError(
                "prefill_chunk must be >= 1: the two-phase engine "
                "(bucketed prefill + decode, prefill_chunk=0) is gone "
                "— every prompt streams through the mixed step")
        tb = _knob("token_budget", "FLAGS_generation_token_budget", int)
        # One step ahead of the host (_mixed_ahead) wherever the step
        # allows it: without speculation (a draft is verified against
        # tokens the host has not seen yet). No flag: the argument is
        # for tests, which hold the streams with it to the streams
        # without.
        can_look = not self.spec_tokens
        self.lookahead = int(can_look if lookahead is None else lookahead)
        if self.lookahead not in (0, 1):
            raise ValueError("lookahead must be 0 or 1")
        if self.lookahead and not can_look:
            raise ValueError(
                "lookahead dispatches the mixed step ahead of the "
                "tokens it feeds on — it needs "
                "FLAGS_generation_spec_tokens 0")
        # auto budget leaves room for every lane's k draft slots so
        # speculation never starves prefill chunks
        self.token_budget = (
            tb if tb > 0 else
            self.decode_width * (1 + self.spec_tokens)
            + self.prefill_chunk)
        if self.token_budget < self.decode_width:
            raise ValueError(
                "token_budget %d < decode_width %d: every decode "
                "lane needs a slot each step" % (self.token_budget,
                                                 self.decode_width))
        # sampler rows: 1 + k per lane (a lane's plain-decode slot
        # plus its verify slots) — the mixed fn gathers these out of
        # the t-slot logits so the sampler's sort never runs on
        # prompt/padding slots; with spec off this is exactly the
        # PR-10 per-lane sampler cost
        self.sample_width = self.decode_width * (1 + self.spec_tokens)
        self.kv = KVCacheManager(nb, bs)
        # table width: enough blocks for a max-length context
        self.max_blocks_per_seq = self.kv.blocks_for_tokens(
            cfg.max_seq_len)
        # the key axis of a lane's paged view. NaiveGenerator takes it
        # (model.forward_full, `attn_lanes`) so that the oracle attends
        # over an axis as wide as the engine's: the token-stream tests
        # (tests/test_generation.py) compare the two
        self.attn_lanes = self.max_blocks_per_seq * bs
        # device pools: made below by _restore_pools, once the draft
        # model's config is known (it makes whichever are missing)
        self.k_pools = self.v_pools = None
        for name in self._kv_names:
            setattr(self, name, None)
        self.k_scales = self.v_scales = None
        # cross-request prefix cache (the pool's block is the unit)
        pc_on = bool(prefix_cache if prefix_cache is not None
                     else get_flag("FLAGS_generation_prefix_cache"))
        self.prefix_cache = PrefixCache(self.kv) if pc_on else None
        # drafter for speculative decoding: "ngram" is a host-side
        # prompt-lookup (zero device cost); "model" runs a small draft
        # decoder over its OWN paged pools indexed by the same tables
        # (self.draft_kind resolved above, with the policy key)
        self.draft_cfg = draft_cfg
        self.draft_params = None
        self.dk_pools = self.dv_pools = None
        if self.spec_tokens and self.draft_kind == "model":
            if draft_cfg is None or draft_params is None:
                raise ValueError(
                    "draft='model' needs draft_cfg and draft_params")
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft vocab %d != target vocab %d"
                    % (draft_cfg.vocab_size, cfg.vocab_size))
            if draft_cfg.max_seq_len < cfg.max_seq_len:
                raise ValueError(
                    "draft max_seq_len %d < target %d (pos_emb must "
                    "cover every verified position)"
                    % (draft_cfg.max_seq_len, cfg.max_seq_len))
            self.draft_params = jax.tree.map(jnp.asarray, draft_params)
        elif self.spec_tokens and self.draft_kind != "ngram":
            raise ValueError("unknown draft kind %r (ngram|model)"
                             % self.draft_kind)
        self._restore_pools()
        # compiled-step registry: dict miss == an engine compilation
        # (STAT_generation_compile — the zero-steady-state-recompile
        # pin counts THIS, plus the fixed shapes make jax's own cache
        # hit whenever this dict does)
        self._fns: Dict[Any, Any] = {}
        # decode lanes (fixed width): parallel host arrays
        w = self.decode_width
        self._lane_seq: List[Optional[_Seq]] = [None] * w
        self._tables = np.zeros((w, self.max_blocks_per_seq), np.int32)
        self._temps = np.zeros((w,), np.float32)
        self._top_ks = np.zeros((w,), np.int32)
        self._top_ps = np.ones((w,), np.float32)
        self._seeds = np.zeros((w,), np.int32)
        self._pending: List[_Seq] = []     # admitted, awaiting prefill
        self._admit_counter = 0
        # lookahead: the mixed step that runs on the device while the
        # host plans the next one — (tokens on the device, decode
        # plan, chunk plan, time of dispatch); None when none is out
        self._inflight = None
        self._t_collected = 0.0
        # what a family's step reports beside its tokens (int32
        # numbers after the sample rows of the step's one result, so
        # that they cost no fetch of their own; `cfg.record_step_stats`
        # reads them on the host): 0 for a family that reports nothing
        self._stats_len = int(getattr(cfg, "step_stats_len", 0))
        # what a step feeds on where the host gives every token itself
        self._no_prev = jnp.zeros((self.sample_width + self._stats_len,),
                                  jnp.int32)
        # per-request error sink: the scheduler points this at the
        # request's future; the bare engine re-raises
        self.on_request_error = None
        # flipped by warmup(): the GenerationPool's /readyz probe
        self._warmed = False
        self._publish_quant_gauges()
        self._publish_autotune_gauges()

    # --- the device pools ----------------------------------------------

    def _pool_specs(self) -> Dict[str, tuple]:
        """attribute -> (shape, dtype, fill) of every device pool this
        engine holds: the ONE place the cache's geometry is written
        down, from what the model family's config says of it
        (`kv_layers`, `kv_row`, `kv_heads`; a looped family holds
        passes x layers of cache, a head need not be hidden / heads).
        The family's step takes a K and a V pool, or the pools it
        declares (`cfg.kv_pools`: the latent family's ONE pool of rows
        whose first columns are the values). A pool is `[kv_layers, N,
        block_size, kv_row]`, a row `kv_heads * head_dim` wide:
        the heads' two axes are kept FLAT, because the TPU's
        default layout of a `[..., heads, 64]` array makes the block
        axis N the minor one, and then neither the step's scatter nor
        the block-table gather can use the array as it lies (each
        copied the whole pool, PERF.md PR 28). Flat, a block is one
        contiguous `[block_size, hidden]` tile."""
        cfg, nb, bs = self.cfg, self.kv.num_blocks, self.kv.block_size
        shape = (cfg.kv_layers, nb, bs, cfg.kv_row)
        if self.kv_dtype in _PLAIN_KV:
            dt = _PLAIN_KV[self.kv_dtype]
            specs = {n: (shape, dt, 0) for n in self._kv_names}
        else:
            # quantized pool + per-token-per-head fp32 absmax scale
            # pool (quant.quantize_kv_rows). Scales init to ONE so a
            # trash-block / never-written row dequantizes its zero
            # payload to exact 0.0, same as the fp32 pools
            dt = _quant.storage_dtype(self.kv_dtype)
            sshape = (cfg.kv_layers, nb, bs, cfg.kv_heads)
            specs = {"k_pools": (shape, dt, 0),
                     "v_pools": (shape, dt, 0),
                     "k_scales": (sshape, jnp.float32, 1),
                     "v_scales": (sshape, jnp.float32, 1)}
        if self.draft_params is not None:
            dshape = (self.draft_cfg.kv_layers, nb, bs,
                      self.draft_cfg.kv_row)
            specs["dk_pools"] = (dshape, jnp.float32, 0)
            specs["dv_pools"] = (dshape, jnp.float32, 0)
        return specs

    def _restore_pools(self) -> List[str]:
        """Make every pool that is missing or dead, as a new engine
        holds it (zeros; scale pools ones). The pools are DONATED to
        every program that writes them, so a fault raised while such a
        call runs can leave the engine holding deleted arrays:
        GenerationPool._reset_engine and the draft step's fault path
        call this. Returns the names it made."""
        made = []
        for name, (shape, dtype, fill) in self._pool_specs().items():
            cur = getattr(self, name)
            if cur is None or cur.is_deleted():
                setattr(self, name, jnp.full(shape, fill, dtype))
                made.append(name)
        return made

    # --- quantized serving (ISSUE 15) ----------------------------------

    def kv_pool_bytes(self) -> int:
        """Total device bytes of the K/V block pools, scale pools
        included — the fixed budget the capacity bench holds constant
        across dtypes."""
        return int(sum(getattr(self, n).nbytes
                       for n in self._program_pools("mixed")))

    def kv_bytes_per_seq(self) -> int:
        """Pool bytes one max-length sequence occupies (payload +
        scales over its max_blocks_per_seq table span) — the value
        behind GAUGE_kv_bytes_per_seq. Read off the pool spec: a
        block's bytes in every pool the mixed step writes (the
        drafter's are its own), times the table span."""
        per_block = sum(
            math.prod(shape) // shape[1] * jnp.dtype(dtype).itemsize
            for name, (shape, dtype, _) in self._pool_specs().items()
            if name in self._program_pools("mixed"))
        return int(per_block * self.max_blocks_per_seq)

    def kv_capacity_seqs(self) -> int:
        """Concurrent max-length sequences the pool admits (block 0 is
        the trash block). At a FIXED byte budget a quantized pool
        affords ~4x the blocks, so this is where the 2-4x concurrency
        headline lands (bench.py quantized_serving gates >= 2x)."""
        return (self.kv.num_blocks - 1) // self.max_blocks_per_seq

    def _publish_quant_gauges(self) -> None:
        """(Re)publish the quant gauges. Called at construction AND by
        the scheduler's _reset_engine, so a post-fault rebuild retracts
        stale values (tests/test_failpoints.py pins this)."""
        gauge_set("GAUGE_kv_bytes_per_seq", self.kv_bytes_per_seq())
        gauge_set("GAUGE_kv_layers", self.cfg.kv_layers)
        gauge_set("GAUGE_kv_capacity_seqs", self.kv_capacity_seqs())
        gauge_set("GAUGE_quant_weight_bytes_saved",
                  _quant.weight_bytes_saved(self.params))

    def _publish_autotune_gauges(self) -> None:
        """(Re)publish the autotune gauges for this engine's resolved
        policy entry. Called at construction AND by the scheduler's
        _reset_engine (tests/test_autotune.py pins the retraction) —
        an untuned engine publishes zeros, which IS the retraction."""
        e = self._policy_entry or {}
        gauge_set("GAUGE_autotune_active", 1.0 if e else 0.0)
        gauge_set("GAUGE_autotune_step_time_us",
                  float(e.get("step_time_us", 0.0)))
        gauge_set("GAUGE_autotune_trials", float(e.get("trials", 0.0)))

    # --- compiled-step registry ---------------------------------------

    def _get_fn(self, kind: str):
        fn = self._fns.get(kind)
        if fn is not None:
            return fn
        with _kernel_form(self.kernel):
            fn = self._build_fn(kind)
        self._fns[kind] = fn
        return fn

    def _build_fn(self, kind: str):
        stat_add("STAT_generation_compile")
        cfg = self.cfg
        if kind == "mixed":
            # ONE executable for every step of the engine: T =
            # token_budget SLOTS of (block-table row, position, token)
            # — a decode lane's next token, one of its k draft tokens
            # to verify, or one prompt token of a prefill chunk;
            # forward_paged scatters every slot's K/V before attending,
            # so chunk-mates (and a lane's draft slots) see each other
            # and the step is the ragged mixed batch of the paper. The
            # sampler reads S = decode_width * (1 + spec_tokens) rows
            # through sample_slots — 1 + k per LANE (PR 14: a lane's
            # plain-decode slot plus its verify slots), each carrying
            # the lane's sampling params and the slot's absolute token
            # index as the fold_in step. That index is what makes a
            # verified draft sample bitwise-identical to the plain
            # decode sample at the same position; the gather keeps the
            # sampler's sort cost off the (much wider) padding slots.
            # The host decides which sample rows are emitted.
            # Quantized KV threads the scale pools through the SAME
            # executable (5-tuple state) — the dequant runs inside the
            # attention kernel's online-softmax loop, not as a separate
            # pass, so the step count and shapes never change.
            # The step's ten host arrays travel as TWO (one int32, one
            # float32: _pack_mixed), split again in here: each array
            # handed over costs the host an allocation, a linearize and
            # a transfer of its own, serial with the device (3.1 ms of
            # dispatch a step, PERF.md PR 29).
            m = self.max_blocks_per_seq
            t = self.token_budget
            sw = self.sample_width
            quant_kv = self.k_scales is not None
            n_stats = self._stats_len
            n_kv = len(self._kv_names)

            def raw(params, *rest):
                pools, (prev, ints, floats) = rest[:-3], rest[-3:]
                tables, positions, tokens, feed_rows, sample_slots, \
                    tks, seeds, steps = jnp.split(ints, np.cumsum(
                        (t * m, t, t, t, sw, sw, sw)).tolist())
                # lookahead: a slot whose token the host has not seen
                # yet takes it from the previous step's samples, which
                # never left the device (row feed_rows[slot]; -1: the
                # host's own token)
                tokens = jnp.where(feed_rows >= 0,
                                   prev[jnp.maximum(feed_rows, 0)],
                                   tokens)
                temps, tps = floats[:sw], floats[sw:]
                scales = dict(k_scale_pools=pools[n_kv],
                              v_scale_pools=pools[n_kv + 1]) \
                    if quant_kv else {}
                tables = tables.reshape(t, m)
                if n_stats:
                    # the slots that carry a token: an idle slot's
                    # table is the trash block throughout
                    scales["live"] = tables[:, 0] != TRASH_BLOCK
                out = cfg.forward_paged(
                    params, *pools[:n_kv], tables,
                    positions, tokens, **scales)
                with jax.named_scope("sampler"):
                    nxt = sample_tokens(out[0][sample_slots], temps,
                                        tks, tps, seeds, steps)
                if n_stats:
                    nxt = jnp.concatenate(
                        [nxt, out[-1].reshape(n_stats).astype(nxt.dtype)])
                    out = out[:-1]
                return (nxt,) + tuple(out[1:])
            avals = (jax.tree.map(_sds, self.params),) + tuple(
                _sds(getattr(self, n))
                for n in self._program_pools(kind)) + (
                jax.ShapeDtypeStruct((sw + n_stats,), jnp.int32),
                jax.ShapeDtypeStruct((t * m + 3 * t + 4 * sw,), jnp.int32),
                jax.ShapeDtypeStruct((2 * sw,), jnp.float32))
        elif kind in ("cow", "draft_cow"):
            # copy-on-write: clone one pool block's rows (every layer)
            # before a write would mutate a shared block. Scalar
            # src/dst keep it ONE executable for any block pair. A
            # quantized target pool clones its scale rows in the same
            # executable (draft pools are always fp32). The pools are
            # donated: the program writes one block (590 KB at GPT-2
            # widths) into the arrays it was given and returns them.
            pools = tuple(getattr(self, n)
                          for n in self._program_pools(kind))
            n_pools = len(pools)

            def raw(*args):
                src, dst = args[n_pools:]
                with jax.named_scope("kv_copy_on_write"):
                    return tuple(p.at[:, dst].set(p[:, src])
                                 for p in args[:n_pools])
            avals = tuple(_sds(p) for p in pools) + (
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
        elif kind == "draft_mixed":
            # the draft model's step over the SAME slot layout and the
            # same block tables, writing its own pools. Greedy argmax:
            # draft choices only gate ACCEPTANCE, never token values,
            # so the draft needs no sampler parity.
            dcfg = self.draft_cfg

            def raw(params, kp, vp, tables, positions, tokens):
                logits, kp2, vp2 = dcfg.forward_paged(
                    params, kp, vp, tables, positions, tokens)
                with jax.named_scope("sampler"):
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return nxt, kp2, vp2
            m = self.max_blocks_per_seq
            t = self.token_budget
            i32 = jnp.int32
            avals = (
                jax.tree.map(_sds, self.draft_params),
                _sds(self.dk_pools), _sds(self.dv_pools),
                jax.ShapeDtypeStruct((t, m), i32),
                jax.ShapeDtypeStruct((t,), i32),
                jax.ShapeDtypeStruct((t,), i32),
            )
        else:
            raise ValueError(kind)
        return self._aot_or_jit(kind, raw, avals)

    def _program_pools(self, kind: str) -> tuple:
        """Names of the pools `kind`'s program takes, writes and
        returns, in the order of its arguments and results."""
        if kind.startswith("draft"):
            return ("dk_pools", "dv_pools")
        if self.k_scales is not None:
            return self._kv_names + ("k_scales", "v_scales")
        return self._kv_names

    def _pool_argnums(self, kind: str) -> tuple:
        """Positions of those pools among the program's arguments
        (_build_fn's signatures: first in `cow` and `draft_cow`, after
        the weights elsewhere): what it donates."""
        first = 0 if kind.endswith("cow") else 1
        return tuple(range(first, first + len(self._program_pools(kind))))

    def _aot_or_jit(self, kind: str, raw, avals):
        """Route the step through the persistent AOT program cache
        (PR 1) when a cache dir resolves; plain jit otherwise. Both
        paths register with the XLA program accounting registry
        (core/program_accounting.py) so /programz shows every step
        with compile-time flops/bytes.
        Every program that returns new pools DONATES the old ones, on
        both paths, so the update happens in the arrays the engine
        holds: after a call the pool arrays passed in are dead and the
        caller keeps the ones returned."""
        tag = "generation_%s" % kind
        base = (self.draft_cfg.meta() if kind.startswith("draft")
                else self.cfg.meta())
        # v=4: ISSUE-16 adaptive dispatch — kern is the RESOLVED
        # kernel form (a pin, the policy's winner or the backend's
        # own, baked in through the kernel_form override: no flag of
        # lowering_snapshot tells it), and
        # policy is the entry label that produced this geometry, which
        # is what makes zero-steady-state-recompiles provable across a
        # restart: a process that reloads the persisted policy builds
        # the SAME meta, hits the SAME fingerprint, and loads the AOT
        # trace the tuned process exported. v=3 (ISSUE 15) added
        # qm/kvq so an fp32 cached program can never serve a quantized
        # checkpoint; samp rides along because two engines can share
        # every other dimension yet differ in spec_tokens. v=5: the
        # pools are donated and hold the heads' axes flat, so no entry
        # stored before that is served. v=6: the pools' geometry is
        # the model family's (kv_layers x kv_row, in `base`), and the
        # weights' dtype rides along (wdt): a float32 and a bfloat16
        # checkpoint of one config are two programs. v=7: the mixed
        # step takes the previous step's samples and `feed_rows`.
        weights = (self.draft_params if kind.startswith("draft")
                   else self.params)
        meta = dict(base, kind=kind, v=7,
                    wdt=sorted({str(w.dtype)
                                for w in jax.tree.leaves(weights)}),
                    blocks=self.kv.num_blocks,
                    block_size=self.kv.block_size,
                    width=self.decode_width,
                    table=self.max_blocks_per_seq,
                    lanes=self.attn_lanes,
                    chunk=self.prefill_chunk,
                    slots=self.token_budget,
                    samp=self.sample_width,
                    qm=self.quant_mode,
                    kvq=self.kv_dtype,
                    kern=self.kernel,
                    policy=(self._policy_entry or {}).get("label", ""))
        # the device trace's `XLA Modules` line shows the step by name
        # (`jit_generation_mixed...`), with the cache as without
        raw.__name__ = tag
        donate = self._pool_argnums(kind)
        cache_dir = program_cache.resolve_dir(self._program_cache_dir)
        if cache_dir is not None:
            fp = program_cache.fn_fingerprint("generation_step", meta)
            fn = program_cache.exported_entry(cache_dir, fp, raw, avals,
                                              tag=tag, meta=meta,
                                              donate_argnums=donate)
            if fn is not None:
                return fn
        from ..core import program_accounting
        return program_accounting.accounted(
            jax.jit(raw, donate_argnums=donate), avals,
            tag=program_accounting.safe_tag(tag),
            key=program_accounting.key_token(sorted(meta.items())),
            meta=meta)

    def warmup(self) -> dict:
        """Compile-ahead: the ONE mixed-step executable, the
        copy-on-write clone where the prefix cache is on, the draft
        model's two where it drafts. Steady state then never compiles.
        The engine's resolved kernel form is pinned for anything
        traced here (the rare accounted-compile fallback traces at
        first call, inside this block)."""
        with _kernel_form(self.kernel):
            return self._warmup_inner()

    def _warmup_inner(self) -> dict:
        report = {}
        t0 = time.perf_counter()
        self._warm_mixed()
        report["mixed"] = round(time.perf_counter() - t0, 4)
        if self.prefix_cache is not None:
            # the COW copy must be warm too: the first write into a
            # shared block happens in steady state, and the
            # zero-steady-state-recompile pin counts it
            t0 = time.perf_counter()
            self._warm_cow("cow")
            report["cow"] = round(time.perf_counter() - t0, 4)
        if self.draft_params is not None:
            t0 = time.perf_counter()
            self._warm_draft()
            self._warm_cow("draft_cow")
            report["draft"] = round(time.perf_counter() - t0, 4)
        self._warmed = True
        return report

    def _warm_mixed(self) -> None:
        t, sw = self.token_budget, self.sample_width
        zt = np.zeros((t,), np.int32)
        zs = np.zeros((sw,), np.int32)
        # every slot writes the trash block
        self._run("mixed", self._no_prev, *_pack_mixed(
            np.zeros((t, self.max_blocks_per_seq), np.int32), zt, zt,
            zt - 1, zs, np.zeros((sw,), np.float32), zs,
            np.ones((sw,), np.float32), zs, zs))

    def _warm_cow(self, kind: str) -> None:
        # trash-block self-copy: compiles the clone, mutates nothing
        # anyone reads
        z = jnp.asarray(TRASH_BLOCK, jnp.int32)
        self._run(kind, z, z)

    def _run(self, kind: str, *rest):
        """Run `kind`'s compiled program on the pools it writes and
        KEEP the pools it returns: the arrays passed in are donated
        and dead after the call. `mixed` and `draft_mixed`
        take (weights, *pools, *rest) and return (result, *pools): the
        result is handed back; `cow` and `draft_cow` take (*pools,
        *rest) and return the pools alone."""
        names = self._program_pools(kind)
        args = tuple(getattr(self, n) for n in names) + rest
        if kind.endswith("cow"):
            out = (None,) + tuple(self._get_fn(kind)(*args))
        else:
            out = self._get_fn(kind)(
                self.draft_params if kind.startswith("draft")
                else self.params, *args)
        for n, a in zip(names, out[1:]):
            setattr(self, n, a)
        return out[0]

    def _warm_draft(self) -> None:
        t = self.token_budget
        zt = jnp.zeros((t,), jnp.int32)
        self._run("draft_mixed",
                  jnp.zeros((t, self.max_blocks_per_seq), jnp.int32),
                  zt, zt)

    # --- admission -----------------------------------------------------

    def submit(self, req: GenerationRequest) -> None:
        """Validate + queue a request. Raises ValueError on a request
        that can never run (too long, empty) — per-request isolation:
        a bad request touches no shared state."""
        prompt = list(int(t) for t in req.prompt)
        if not prompt:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = len(prompt) + int(req.max_new_tokens)
        if total > self.cfg.max_seq_len:
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds max_seq_len "
                "%d" % (len(prompt), req.max_new_tokens,
                        self.cfg.max_seq_len))
        if self.kv.blocks_for_tokens(total) > self.kv.num_blocks - 1:
            raise ValueError(
                "request needs %d blocks but the pool only has %d "
                "(FLAGS_generation_kv_blocks) — it could never run"
                % (self.kv.blocks_for_tokens(total),
                   self.kv.num_blocks - 1))
        # bare-engine use opens the trace here; pooled requests arrive
        # with the pool's trace already attached (ONE flag lookup per
        # request either way — begin() is the only lookup site)
        tr = req.trace if req.trace is not None \
            else _tr.begin("generation")
        req = replace(req, prompt=prompt, trace=tr)
        tr.stage("admit")
        seq = _Seq(req, self._admit_counter)
        self._admit_counter += 1
        self._pending.append(seq)
        stat_add("STAT_generation_requests")

    @property
    def active_count(self) -> int:
        return sum(s is not None for s in self._lane_seq)

    @property
    def pending_count(self) -> int:
        return len(self._pending)

    @property
    def idle(self) -> bool:
        return self.active_count == 0 and not self._pending

    # --- the step ------------------------------------------------------

    def step(self) -> List[GenerationResult]:
        """One scheduler tick: admit pending requests into free lanes,
        advance every active lane (one mixed batch), retire
        finished sequences. Returns the finished results (possibly
        empty)."""
        # host spans on the profiler's clock (telemetry.py): the five
        # children cover the step, what is left is its self time
        with _tm.span("pt/engine/step", track="generation"):
            with _tm.span("pt/engine/admit", track="generation"):
                self._admit()
            if self.active_count == 0:
                # lookahead: a step whose riders all ended at their EOS
                # has nobody left to hand a token to
                self._inflight = None
                return []
            if self.lookahead:
                return self._mixed_ahead()
            return self._mixed_once()

    def _admit(self) -> None:
        """Admit pending requests into free lanes, oldest first (the
        preemption replay path re-queues at the FRONT, so an evicted
        in-progress request always beats a never-started one — the
        fairness contract). Pool exhaustion stops admission (decode
        continues; completions will free blocks).

        Error handling is two-tier: a never-started request whose
        admission raises is killed (per-request isolation), but a
        REPLAYED request (evictions > 0) already streamed tokens to a
        client — killing it on a transient admission fault (e.g. an
        injected generation.kv_alloc raise) would turn a recoverable
        hiccup into a dropped stream AND let newer requests overtake
        it. Replayed admission faults are retried (request stays at the
        front, STAT_generation_replay_retries) up to
        _REPLAY_ADMIT_RETRIES consecutive failures before the kill."""
        for lane in range(self.decode_width):
            if not self._pending or self._lane_seq[lane] is not None:
                continue
            seq = self._pending[0]
            try:
                if not self._admit_chunked(seq, lane):
                    break                      # pool full: try later
            except Exception as e:
                if seq.evictions and \
                        seq.admit_failures < _REPLAY_ADMIT_RETRIES:
                    seq.admit_failures += 1
                    stat_add("STAT_generation_replay_retries")
                    break                      # keep at front, retry
                # per-request isolation: an admission failure kills
                # only this request
                self._pending.pop(0)
                stat_add("STAT_generation_errors")
                seq.req.trace.finish(error=e)
                self._deliver_error(seq, e)
                continue
            self._pending.pop(0)
        gauge_set("GAUGE_generation_active_seqs", self.active_count)

    def _admit_chunked(self, seq: _Seq, lane: int) -> bool:
        """Park `seq` in `lane` for chunked prefill: walk the prefix
        cache for the longest cached block chain, attach those shared
        blocks plus private blocks for the rest of the prompt + the
        first decode token all-or-nothing (a half-provisioned prompt
        would stall mid-prefill holding blocks), then let the mixed
        step stream in the UNCACHED suffix. Returns False (untouched
        state) when the pool can't hold it yet.

        The hit always re-runs at least the last prompt token (an
        exact-duplicate prompt still needs its first-token logits);
        that re-run's K/V write is bitwise-identical to the cached
        value, and if it lands in a still-shared block the COW in
        _provision clones it first. A prefix_lookup fault degrades to
        cold prefill — the cache is read-only here, so it can't be
        poisoned."""
        n = len(seq.req.prompt)
        pc = self.prefix_cache
        t0 = time.perf_counter()
        cached_use = 0
        shared: List[int] = []
        if pc is not None:
            if seq.pkeys is None:
                seq.pkeys = pc.keys_for(seq.req.prompt)
            try:
                hit = pc.match(seq.req.prompt)
            except Exception:
                hit = None
            if hit is not None:
                cached_tokens, blocks = hit
                cached_use = min(int(cached_tokens), n - 1)
                shared = blocks[:self.kv.blocks_for_tokens(cached_use)]
        private_need = self.kv.blocks_for_tokens(n + 1) - len(shared)
        if private_need > self.kv.free_blocks:
            # pool pressure: cold cached prefixes go before we defer
            if pc is None or not pc.evict_for(private_need):
                return False
        # before any state mutation: an injected raise leaves the
        # engine consistent (the request is still pending)
        failpoint("generation.prefill")
        tr = seq.req.trace
        tr.stage("prefill_start")
        if seq.evictions:
            tr.event("replay", evictions=seq.evictions)
        sid = id(seq)
        self.kv.attach(sid, shared, private_need)
        seq.lane = lane
        seq.prefilled = cached_use
        seq.ctx = cached_use
        seq.published = cached_use
        self._lane_seq[lane] = seq
        sp = seq.req.sampling
        self._tables[lane] = self.kv.table(sid, self.max_blocks_per_seq)
        self._temps[lane] = sp.temperature
        self._top_ks[lane] = sp.top_k
        self._top_ps[lane] = sp.top_p
        self._seeds[lane] = sp.seed
        if pc is not None:
            if cached_use:
                stat_add("STAT_generation_prefix_hits")
                stat_add("STAT_generation_prefix_hit_tokens",
                         cached_use)
                tr.event("prefix_hit", tokens=cached_use,
                         blocks=len(shared))
            else:
                stat_add("STAT_generation_prefix_misses")
            timer_observe("TIMER_generation_prefix_admit_us",
                          (time.perf_counter() - t0) * 1e6)
        stat_add("STAT_generation_prefills")
        return True

    def _mixed_once(self) -> List[GenerationResult]:
        """One MIXED step: assemble up to token_budget
        slots — every decoding lane's next token first (decode never
        waits on a prefill: the no-head-of-line-blocking contract),
        then up to prefill_chunk prompt tokens per prefilling lane in
        lane order — and run the single compiled mixed executable.
        Unused slots spin on the trash block (counted in
        STAT_generation_pad_tokens).

        Everything before the compiled call only reads engine state, so
        a failpoint raise (generation.decode at the top,
        generation.prefill_chunk between chunks) aborts the step with
        nothing mutated: a caller that catches the InjectedFault can
        call step() again and the batch resumes exactly where it was —
        no token duplication, the basis of the mid-prompt fault
        recovery test."""
        finished: List[GenerationResult] = []
        plan = self._plan_mixed(finished)
        if plan is None:
            return finished
        packed, decode_plan, chunk_plan = plan
        t0 = time.perf_counter()
        with _tm.trace_scope(self._riders(decode_plan, chunk_plan)):
            with _tm.span("pt/engine/dispatch", track="generation"):
                nxt = self._run("mixed", self._no_prev, *packed)
            with _tm.span("pt/engine/fetch", track="generation"):
                nxt = self._fetched(nxt)
        dt_us = (time.perf_counter() - t0) * 1e6
        self._emit_mixed(nxt, dt_us, decode_plan, chunk_plan, finished)
        return finished

    def _fetched(self, nxt) -> np.ndarray:
        """A step's result on the host: its sample rows, after what
        the family's step reported beside them went to the family's
        counters (`_stats_len`). The wait for the device lies under
        `pt/device/wait`; what is left of the caller's `fetch` is the
        copy to the host, asked for before the wait so that it follows
        the step on the device as `np.asarray` alone would have it."""
        nxt.copy_to_host_async()
        with _tm.span("pt/device/wait", track="generation"):
            nxt.block_until_ready()
        nxt = np.asarray(nxt)
        if self._stats_len:
            self.cfg.record_step_stats(nxt[self.sample_width:])
        return nxt

    def _riders(self, decode_plan, chunk_plan) -> Optional[str]:
        """The trace ids of the requests that ride a step."""
        if not _tm.enabled():
            return None
        return ",".join(
            tid for tid in (p[1].req.trace.trace_id
                            for p in decode_plan + chunk_plan) if tid)

    def _plan_mixed(self, finished: List[GenerationResult]):
        """The host's half of a mixed step before the compiled call:
        -> (the two packed host arrays, decode plan, chunk plan), or
        None where no lane has anything to run. Sequences retired on
        the way are appended to `finished`. Reads engine state only
        (see _mixed_once), but for the block ledger."""
        with _tm.span("pt/engine/plan", track="generation"):
            failpoint("generation.decode")
            # retire sequences whose PREVIOUS token already terminated them
            for lane, seq in enumerate(self._lane_seq):
                if seq is None:
                    continue
                done = self._finish_reason(seq)
                if done is not None:
                    finished.append(self._retire(lane, done))
            t = self.token_budget
            m = self.max_blocks_per_seq
            # per-lane draft budget this step (0 with speculation off)
            s_cap = self._spec_caps()
            # provision every lane's write horizon: block extension plus
            # copy-on-write of shared blocks in a write range. Pool
            # exhaustion evicts cold cached prefixes LRU-first; only a dry
            # cache preempts the youngest sequence. Re-running _provision
            # after either is idempotent (already-extended / already-COWed
            # lanes are no-ops).
            while True:
                try:
                    self._provision(s_cap)
                    break
                except BlockPoolExhausted:
                    if self.prefix_cache is not None and \
                            self.prefix_cache.evict_for(1):
                        continue
                    if self._inflight is not None:
                        # lookahead: what the step on the device ends
                        # gives its blocks back before anyone is
                        # preempted (and a replay loses no token that
                        # is still on the device). A cold cached prefix
                        # goes first: that needs no wait for the device
                        finished.extend(self._collect())
                        continue
                    if not self._preempt_youngest():
                        raise
            decode_lanes = []
            prefill_lanes = []
            for ln, s in enumerate(self._lane_seq):
                if s is None or self._spent(s):
                    continue
                if s.prefilled >= len(s.req.prompt):
                    decode_lanes.append(ln)
                else:
                    prefill_lanes.append(ln)
            if not decode_lanes and not prefill_lanes:
                if self._inflight is None:
                    gauge_set("GAUGE_generation_active_seqs", 0)
                return None
            # chunk plan BEFORE drafting, using the conservative s_cap slot
            # layout: the model drafter's call 0 ingests these chunk tokens
            # into the draft pools, so the plan must be fixed first. If the
            # drafter then proposes fewer tokens the slack slots just pad.
            slot = len(decode_lanes) + sum(s_cap.get(ln, 0)
                                           for ln in decode_lanes)
            chunk_plan = []              # (lane, seq, start, take)
            for ln in prefill_lanes:
                seq = self._lane_seq[ln]
                n = len(seq.req.prompt)
                take = min(self.prefill_chunk, n - seq.prefilled, t - slot)
                if take <= 0:
                    continue
                chunk_plan.append((ln, seq, seq.prefilled, take))
                slot += take
            drafts = self._propose(decode_lanes, s_cap, chunk_plan)
            tables = np.full((t, m), TRASH_BLOCK, np.int32)
            positions = np.zeros((t,), np.int32)
            tokens = np.zeros((t,), np.int32)
            # lookahead: the sampler row of the step on the device that
            # holds this slot's token (-1: `tokens` holds it)
            feed_rows = np.full((t,), -1, np.int32)
            # sampler arrays are [sample_width]: each LANE owns 1 + k
            # consecutive rows (rows ln*(1+k) .. ln*(1+k)+k); a decode lane
            # uses rows 0..len(drafts) for its verify chain, a prefill lane
            # uses row 0 for its chunk's last slot. Unused rows gather the
            # trash slot's logits (greedy, discarded on the host).
            sw = self.sample_width
            rpl = 1 + self.spec_tokens          # sampler rows per lane
            sample_slots = np.zeros((sw,), np.int32)
            temps = np.zeros((sw,), np.float32)
            tks = np.zeros((sw,), np.int32)
            tps = np.ones((sw,), np.float32)
            seeds = np.zeros((sw,), np.int32)
            steps = np.zeros((sw,), np.int32)
            slot = 0
            ends = []                   # each planned lane's last slot
            # (lane, seq, first sampler row, drafts riding this step)
            decode_plan = []
            for ln in decode_lanes:
                seq = self._lane_seq[ln]
                d = drafts.get(ln, [])[:s_cap.get(ln, 0)]
                row0 = ln * rpl
                if seq.pending:
                    # its last token is still on the device, in the
                    # lane's own sampler row of the step before
                    feed = [0]
                    feed_rows[slot] = row0
                else:
                    feed = [seq.generated[-1]] + d
                base = len(seq.generated) + seq.pending
                for j in range(len(feed)):
                    tables[slot] = self._tables[ln]
                    positions[slot] = seq.ctx + j
                    tokens[slot] = feed[j]
                    sample_slots[row0 + j] = slot
                    temps[row0 + j] = self._temps[ln]
                    tks[row0 + j] = self._top_ks[ln]
                    tps[row0 + j] = self._top_ps[ln]
                    seeds[row0 + j] = self._seeds[ln]
                    # the fold_in step IS the absolute token index — row j
                    # samples exactly what plain decode would at that index
                    steps[row0 + j] = base + j
                    slot += 1
                ends.append(slot - 1)
                decode_plan.append((ln, seq, row0, d))
            for ln, seq, start, take in chunk_plan:
                if seq.prefilled:
                    # between chunks of one prompt — before any token-state
                    # mutation, so a caught raise resumes exactly
                    failpoint("generation.prefill_chunk")
                sp = seq.req.sampling
                for j in range(take):
                    tables[slot] = self._tables[ln]
                    positions[slot] = start + j
                    tokens[slot] = seq.req.prompt[start + j]
                    slot += 1
                ends.append(slot - 1)
                # only the chunk's LAST slot's sample matters (step 0, the
                # first generated token) and only when the chunk completes
                # the prompt — otherwise discarded on the host
                row0 = ln * rpl
                sample_slots[row0] = slot - 1
                temps[row0] = sp.temperature
                tks[row0] = sp.top_k
                tps[row0] = sp.top_p
                seeds[row0] = sp.seed
                steps[row0] = 0
            stat_add("STAT_generation_pad_tokens", t - slot)
            # a step that holds a sampled row takes the sampler's filter
            # branch on the device (sampling.sample_tokens); the steps
            # taken less this counter ran the argmax alone
            if (temps > 0).any():
                stat_add("STAT_generation_sampler_filter_steps")
            # positions the live slots attend over this step: each sees
            # the cache up to and with its own token
            # ... and the pool blocks those contexts span: what the
            # Pallas kernel copies (the reference form gathers every
            # slot's whole table, token_budget x max_blocks_per_seq)
            # ... and the rows each LANE's slots see together, each once:
            # what any kernel must read at the least
            attended, blocks, rows = self._attended(positions[:slot], ends)
            stat_add("STAT_generation_attended_tokens", attended)
            stat_add("STAT_generation_attended_blocks", blocks)
            stat_add("STAT_generation_context_rows", rows)
            if self.k_scales is not None:
                # this step's fresh K/V rows quantize inside the compiled
                # call — the failpoint models a fault in that stage, and it
                # sits BEFORE any state mutation so a caught InjectedFault
                # retries the step cleanly (tests/test_failpoints.py)
                failpoint("generation.kv_quant")
                bs_q = self.kv.block_size
                written = {int(tables[i][positions[i] // bs_q])
                           for i in range(slot)}
                written.discard(TRASH_BLOCK)
                stat_add("STAT_generation_kv_quant_blocks", len(written))
            return (_pack_mixed(tables, positions, tokens, feed_rows,
                                sample_slots, temps, tks, tps, seeds,
                                steps), decode_plan, chunk_plan)

    def _attended(self, positions, ends) -> tuple:
        """(positions attended, pool blocks they span, distinct rows) by
        slots at `positions`, each with its own token; `ends` holds each
        lane's last slot, its slots consecutive positions from the slot
        after the lane before. Distinct rows count a lane's context ONCE
        however many of its slots see it (a prefill chunk's): what a
        kernel must read at the least. In a family whose cache layers
        all see the whole context each is ONE layer's count (every layer
        attends the same); where the family gives a window a cache layer
        (`cfg.kv_windows`, 0: none) it is the SUM over its layers of
        what each really attends, a window layer at most its window a
        slot."""
        if not ends:
            return 0, 0, 0
        bs = self.kv.block_size
        windows = getattr(self.cfg, "kv_windows", None) or (0,)
        last = positions[ends]
        lead = positions[[0] + [e + 1 for e in ends[:-1]]]
        attended = blocks = rows = 0
        for w in sorted(set(windows)):
            first = np.maximum(positions - w + 1, 0) if w else 0
            seen = np.maximum(lead - w + 1, 0) if w else 0
            n = windows.count(w)
            attended += n * int((positions - first + 1).sum())
            blocks += n * int((positions // bs - first // bs + 1).sum())
            rows += n * int((last - seen + 1).sum())
        return attended, blocks, rows

    def _emit_mixed(self, nxt, dt_us, decode_plan, chunk_plan,
                    finished: List[GenerationResult]) -> None:
        """The host's half of a mixed step after the fetch: every
        sampled token to its sequence, the chunks' progress, the
        retirements (appended to `finished`)."""
        rpl = 1 + self.spec_tokens          # sampler rows per lane
        with _tm.span("pt/engine/emit", track="generation"):
            timer_observe("TIMER_generation_mixed_step_us", dt_us)
            emitted = 0
            for ln, seq, row0, d in decode_plan:
                s = len(d)
                if s:
                    stat_add("STAT_generation_spec_proposed", s)
                acc = 0
                # row j's sample is valid iff every draft before it
                # matched (its logits are conditioned on them); emit until
                # the first mismatch. Rejected drafts' K/V writes sit past
                # the new ctx — masked until next step's feed overwrites.
                for j in range(s + 1):
                    tok = int(nxt[row0 + j])
                    seq.ctx += 1
                    seq.generated.append(tok)
                    seq.req.trace.token()
                    emitted += 1
                    done = self._finish_reason(seq)
                    if done is not None:
                        finished.append(self._retire(ln, done))
                        break
                    if j < s:
                        if d[j] != tok:
                            break
                        acc += 1
                if s:
                    stat_add("STAT_generation_spec_accepted", acc)
            for ln, seq, start, take in chunk_plan:
                seq.prefilled = start + take
                seq.ctx = seq.prefilled
                seq.req.trace.event("prefill_chunk", start=start,
                                    width=take)
                self._publish_prefix(seq)
                if seq.prefilled == len(seq.req.prompt):
                    # final chunk: its last slot's logits sampled the first
                    # generated token through the lane's sampler row 0
                    # (step 0: the fold_in NaiveGenerator's first
                    # sample takes, so the streams match).
                    seq.generated.append(int(nxt[ln * rpl]))
                    # TTFT lands at the TRUE first sampled token (first
                    # token() call only; replays re-observe TPOT)
                    seq.req.trace.token()
                    emitted += 1
                    done = self._finish_reason(seq)
                    if done is not None:
                        finished.append(self._retire(ln, done))
            stat_add("STAT_generation_tokens", emitted)
            gauge_set("GAUGE_generation_active_seqs", self.active_count)

    # --- lookahead: one step on the device while the host plans ---------

    def _spent(self, seq: _Seq) -> bool:
        """Its last token is sampled and still on the device: the
        sequence takes no further slot and waits for _collect to retire
        it. (Never true without lookahead: a sequence at its length is
        retired by the emit that appended the token.)"""
        return seq.pending > 0 and \
            len(seq.generated) + seq.pending >= seq.req.max_new_tokens

    def _mixed_ahead(self) -> List[GenerationResult]:
        """One mixed step with lookahead: plan step n from what step
        n-1 WILL have done (positions, chunk progress and lengths do
        not depend on the tokens sampled), dispatch it feeding on the
        tokens step n-1 leaves on the device, and only then fetch those
        tokens and hand them out, while step n runs. The device goes
        from one step into the next; the host's part, and any stall of
        it shorter than a step, is hidden behind the device.

        What the tokens decide is seen one step late: a sequence that
        sampled its EOS in step n-1 still rides step n, and that slot's
        sample is dropped (its K/V row lands in a block the sequence
        owned when the step was dispatched; whoever gets the block next
        writes before reading, and the device runs the steps in order).
        A sequence at its length takes no slot (_spent) and its lane is
        free again one step later than without lookahead. A fault
        raised by the device surfaces at the fetch, one call late, and
        leaves the engine to be reset (GenerationPool does)."""
        finished: List[GenerationResult] = []
        plan = self._plan_mixed(finished)
        if plan is not None:
            packed, decode_plan, chunk_plan = plan
            prev = (self._no_prev if self._inflight is None
                    else self._inflight[0])
            with _tm.trace_scope(self._riders(decode_plan, chunk_plan)), \
                    _tm.span("pt/engine/dispatch", track="generation"):
                nxt = self._run("mixed", prev, *packed)
            t_dispatch = time.perf_counter()
        if self._inflight is not None:
            finished.extend(self._collect())
        if plan is not None:
            with _tm.span("pt/engine/advance", track="generation"):
                self._advance(decode_plan, chunk_plan)
            self._inflight = (nxt, decode_plan, chunk_plan, t_dispatch)
        return finished

    def _advance(self, decode_plan, chunk_plan) -> None:
        """What the step just dispatched does to every sequence that
        rides it, whatever it samples: one more position, one more
        token on the device, the chunk's progress. A sequence the
        collect in between retired (its EOS) is passed over."""
        for ln, seq, _row0, _d in decode_plan:
            if self._lane_seq[ln] is not seq:
                continue
            seq.ctx += 1
            seq.pending += 1
        for ln, seq, start, take in chunk_plan:
            if self._lane_seq[ln] is not seq:
                continue
            seq.prefilled = start + take
            seq.ctx = seq.prefilled
            seq.req.trace.event("prefill_chunk", start=start, width=take)
            self._publish_prefix(seq)
            if seq.prefilled == len(seq.req.prompt):
                seq.pending += 1        # its first token, row 0

    def _collect(self) -> List[GenerationResult]:
        """Fetch what the step on the device sampled (the wait for the
        device, when the host is ahead) and hand each token to its
        sequence; retire what ended. A sequence retired or preempted
        since the step was dispatched has left its lane: its row is
        dropped."""
        nxt, decode_plan, chunk_plan, t_dispatch = self._inflight
        self._inflight = None
        finished: List[GenerationResult] = []
        with _tm.span("pt/engine/fetch", track="generation"):
            nxt = self._fetched(nxt)
        with _tm.span("pt/engine/emit", track="generation"):
            now = time.perf_counter()
            # the step's time is the time it held the pipeline: from
            # its dispatch, or from the fetch before it where the
            # device was still busy with that step
            dt_us = (now - max(t_dispatch, self._t_collected)) * 1e6
            self._t_collected = now
            timer_observe("TIMER_generation_mixed_step_us", dt_us)
            emitted = 0
            rows = [(ln, seq) for ln, seq, _r, _d in decode_plan] + [
                (ln, seq) for ln, seq, start, take in chunk_plan
                if start + take == len(seq.req.prompt)]
            for ln, seq in rows:
                if self._lane_seq[ln] is not seq:
                    continue
                seq.generated.append(int(nxt[ln]))
                seq.pending -= 1
                seq.req.trace.token()
                emitted += 1
                done = self._finish_reason(seq)
                if done is not None:
                    finished.append(self._retire(ln, done))
            stat_add("STAT_generation_tokens", emitted)
            gauge_set("GAUGE_generation_active_seqs", self.active_count)
        return finished

    def _spec_caps(self) -> Dict[int, int]:
        """How many draft tokens each decode lane MAY verify this step:
        bounded by k, the request's remaining token allowance (always
        leave room for the guaranteed plain-decode token), the position
        embedding table, and the slot budget — every decode lane keeps
        its one guaranteed slot, extras granted greedily in lane
        order."""
        k = self.spec_tokens
        if not k:
            return {}
        decode = [ln for ln, s in enumerate(self._lane_seq)
                  if s is not None
                  and s.prefilled >= len(s.req.prompt)]
        budget = self.token_budget - len(decode)
        caps: Dict[int, int] = {}
        for ln in decode:
            seq = self._lane_seq[ln]
            s = min(k,
                    seq.req.max_new_tokens - len(seq.generated) - 1,
                    self.cfg.max_seq_len - 1 - seq.ctx,
                    budget)
            s = max(0, int(s))
            caps[ln] = s
            budget -= s
        return caps

    def _provision(self, s_cap: Dict[int, int]) -> None:
        """Make every lane's write range this step safe: extend block
        tables to the write horizon (a decode lane writes positions
        ctx..ctx+s; a prefill lane stays inside its admission-time
        allocation) and COPY-ON-WRITE any still-shared block the range
        overlaps — ledger swap (kv.cow) plus the compiled one-block
        pool clone, draft pools included. Raises BlockPoolExhausted;
        the caller's retry loop evicts cached prefixes / preempts and
        re-runs this idempotently."""
        bs = self.kv.block_size
        for lane, seq in enumerate(self._lane_seq):
            if seq is None:
                continue
            if self._spent(seq):
                continue                # writes nothing more
            sid = id(seq)
            n = len(seq.req.prompt)
            if seq.prefilled >= n:
                s = s_cap.get(lane, 0)
                lo, hi = seq.ctx, seq.ctx + s
                need = self.kv.blocks_for_tokens(hi + 1)
            else:
                # whole prompt + first decode token were allocated at
                # admission; the chunk writes prefilled..prefilled+take
                lo = seq.prefilled
                hi = min(seq.prefilled + self.prefill_chunk, n) - 1
                need = 0
            while len(self.kv.owned(sid)) < need:
                self.kv.extend(sid)
            owned = self.kv.owned(sid)
            for bi in range(lo // bs, hi // bs + 1):
                if bi < len(owned) and \
                        self.kv.refcount(owned[bi]) > 1:
                    old, new = self.kv.cow(sid, bi)
                    self._copy_block(old, new)
                    stat_add("STAT_generation_prefix_cow_copies")
            self._tables[lane] = self.kv.table(sid,
                                               self.max_blocks_per_seq)

    def _copy_block(self, src: int, dst: int) -> None:
        """Clone one pool block's rows (all layers) src -> dst — the
        device half of copy-on-write."""
        s = jnp.asarray(src, jnp.int32)
        d = jnp.asarray(dst, jnp.int32)
        self._run("cow", s, d)
        if self.draft_params is not None:
            self._run("draft_cow", s, d)

    def _propose(self, decode_lanes: List[int],
                 s_cap: Dict[int, int],
                 chunk_plan) -> Dict[int, List[int]]:
        """Draft up to s_cap[lane] tokens per decode lane. Any fault —
        injected via generation.draft_step or real — degrades THIS step
        to plain decode: drafts only ever gate how many slots verify,
        never what tokens are emitted, so the stream is unchanged."""
        if not self.spec_tokens:
            return {}
        lanes = [ln for ln in decode_lanes if s_cap.get(ln, 0) > 0]
        # the model drafter must still ingest prompt chunks into its
        # pools on prefill-only steps; the ngram drafter has no state
        if not lanes and self.draft_params is None:
            return {}
        try:
            failpoint("generation.draft_step")
            if self.draft_params is not None:
                return self._propose_model(lanes, s_cap, chunk_plan)
            out: Dict[int, List[int]] = {}
            for ln in lanes:
                seq = self._lane_seq[ln]
                d = _ngram_propose(
                    list(seq.req.prompt) + seq.generated, s_cap[ln])
                if d:
                    out[ln] = d
            return out
        except Exception:
            stat_add("STAT_generation_draft_faults")
            # a fault inside the draft step's donated call leaves dead
            # draft pools; cold ones only cost acceptance
            self._restore_pools()
            return {}

    def _propose_model(self, lanes: List[int], s_cap: Dict[int, int],
                       chunk_plan) -> Dict[int, List[int]]:
        """Greedy draft-model proposals: max_s + 1 sequential calls of
        the draft mixed step. Call j feeds each lane's token at
        position ctx + j (call 0 = the last emitted token; later calls
        = the previous call's argmax) — the EXTRA final call consumes
        no proposal but writes the last draft's K/V, so full acceptance
        leaves no permanent gap in the draft pools. Call 0 also ingests
        this step's prompt chunks so the draft pools track the target's
        context. Prefix-cache hits leave the draft pools cold for the
        cached region — acceptance suffers, correctness doesn't; the
        ngram drafter (default) has no such blind spot."""
        t, m = self.token_budget, self.max_blocks_per_seq
        max_s = max((s_cap[ln] for ln in lanes), default=0)
        feeds = {ln: self._lane_seq[ln].generated[-1] for ln in lanes}
        out: Dict[int, List[int]] = {ln: [] for ln in lanes}
        for j in range(max_s + 1):
            tables = np.full((t, m), TRASH_BLOCK, np.int32)
            positions = np.zeros((t,), np.int32)
            tokens = np.zeros((t,), np.int32)
            slot = 0
            slot_of = {}
            for ln in lanes:
                if j > s_cap[ln]:
                    continue
                seq = self._lane_seq[ln]
                tables[slot] = self._tables[ln]
                positions[slot] = seq.ctx + j
                tokens[slot] = feeds[ln]
                slot_of[ln] = slot
                slot += 1
            if j == 0:
                for ln, seq, start, take in chunk_plan:
                    for i in range(take):
                        if slot >= t:
                            break
                        tables[slot] = self._tables[ln]
                        positions[slot] = start + i
                        tokens[slot] = seq.req.prompt[start + i]
                        slot += 1
            nxt = np.asarray(self._run(
                "draft_mixed", jnp.asarray(tables),
                jnp.asarray(positions), jnp.asarray(tokens)))
            for ln, sl in slot_of.items():
                if j < s_cap[ln]:
                    tok = int(nxt[sl])
                    out[ln].append(tok)
                    feeds[ln] = tok
        return {ln: d for ln, d in out.items() if d}

    def _publish_prefix(self, seq: _Seq) -> None:
        """Offer every newly completed block boundary of `seq`'s prompt
        to the prefix cache (the cache increfs the covering blocks).
        The boundaries are whole blocks, so the producer's next write
        lands in a private block and the published ones stay frozen
        with no copy."""
        pc = self.prefix_cache
        if pc is None or seq.pkeys is None:
            return
        sid = id(seq)
        for tokens_b, key in seq.pkeys:
            if tokens_b <= seq.published:
                continue
            if tokens_b > seq.prefilled:
                break
            blocks = self.kv.owned(sid)[
                :self.kv.blocks_for_tokens(tokens_b)]
            pc.insert(key, tokens_b, blocks)
            seq.published = tokens_b

    def _finish_reason(self, seq: _Seq) -> Optional[str]:
        eos = seq.req.eos_token
        if eos is not None and seq.generated and \
                seq.generated[-1] == eos:
            return "eos"
        if len(seq.generated) >= seq.req.max_new_tokens:
            return "length"
        return None

    def _retire(self, lane: int, reason: str) -> GenerationResult:
        seq = self._lane_seq[lane]
        self._lane_seq[lane] = None
        self.kv.free(id(seq))
        self._tables[lane] = TRASH_BLOCK
        self._temps[lane] = 0.0      # an idle lane is a greedy row
        toks = list(seq.generated)
        if reason == "eos":
            toks = toks[:-1]
        seq.req.trace.finish(finish_reason=reason,
                             tokens=len(toks),
                             evictions=seq.evictions)
        return GenerationResult(
            request_id=seq.req.request_id,
            prompt_len=len(seq.req.prompt), tokens=toks,
            finish_reason=reason, evictions=seq.evictions)

    def _preempt_youngest(self) -> bool:
        """Evict the most recently admitted active sequence: free its
        blocks, requeue it at the FRONT of pending (it keeps priority
        over never-started requests). Replay is deterministic — same
        seed, same per-step fold_in — so the regenerated prefix is
        identical and the client observes only latency."""
        cand = None
        for seq in self._lane_seq:
            if seq is None:
                continue
            if cand is None or seq.admit_order > cand.admit_order:
                cand = seq
        if cand is None:
            return False
        lane = cand.lane
        self._lane_seq[lane] = None
        self.kv.evict(id(cand))
        self._tables[lane] = TRASH_BLOCK
        self._temps[lane] = 0.0
        cand.req.trace.event("preempt", lane=lane,
                             ctx=int(cand.ctx),
                             generated=len(cand.generated))
        fresh = _Seq(cand.req, cand.admit_order)
        fresh.evictions = cand.evictions + 1
        self._pending.insert(0, fresh)
        return True

    def _deliver_error(self, seq: _Seq, exc: Exception) -> None:
        """Per-request failure (prefill raised): routed to the
        scheduler's future via on_request_error when set, else
        re-raised (bare-engine usage)."""
        if self.on_request_error is not None:
            self.on_request_error(seq.req, exc)
        else:
            raise exc

    # --- convenience ---------------------------------------------------

    def generate(self, reqs: Sequence[GenerationRequest],
                 max_steps: Optional[int] = None
                 ) -> List[GenerationResult]:
        """Run a batch of requests to completion (continuous batching:
        more requests than decode_width stream through the lanes).
        Results come back in completion order; match by request_id."""
        for i, r in enumerate(reqs):
            if r.request_id is None:
                r = replace(r, request_id=i)
            self.submit(r)
        out: List[GenerationResult] = []
        steps = 0
        # up to ceil(prompt/chunk) steps per request stream the prompt
        # in before its max_new_tokens decode steps
        per_req = 2 * self.cfg.max_seq_len + 4
        limit = (max_steps if max_steps is not None
                 else per_req * max(1, len(reqs)))
        while not self.idle and steps < limit:
            out.extend(self.step())
            steps += 1
        if not self.idle:
            raise RuntimeError("generation did not converge in %d steps"
                               % limit)
        return out


def _ngram_propose(hist: List[int], k: int) -> List[int]:
    """Prompt-lookup drafting (the host-side default): find the most
    recent earlier occurrence of the current m-token suffix (m = 3, 2,
    1) in the request's own prompt + generated history and propose the
    k tokens that followed it. Zero device cost, no draft weights, and
    it thrives exactly where speculation pays — repetitive output that
    echoes the prompt. Proposals only gate acceptance, so a garbage
    guess costs one wasted verify slot, never a wrong token."""
    n = len(hist)
    for mlen in (3, 2, 1):
        if n <= mlen:
            continue
        suffix = hist[n - mlen:]
        for i in range(n - mlen - 1, -1, -1):
            if hist[i:i + mlen] == suffix:
                out = hist[i + mlen:i + mlen + k]
                if out:
                    return list(out)
                break
    return []


# pool dtypes that are stored as they are, with no scale pools beside
_PLAIN_KV = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def _pack_mixed(tables, positions, tokens, feed_rows, sample_slots,
                temps, tks, tps, seeds, steps):
    """The mixed step's ten host arrays as the two it takes them in
    (`_build_fn`, kind `mixed`, splits them again by the same
    order)."""
    return (np.concatenate([tables.ravel(), positions, tokens, feed_rows,
                            sample_slots, tks, seeds, steps]),
            np.concatenate([temps, tps]))


def _sds(v) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(jnp.shape(v), jnp.asarray(v).dtype)


class NaiveGenerator:
    """The O(N^2) baseline the bench compares against: every new token
    re-runs full-context attention over the whole prefix (what PR 4's
    stateless Predictor forces an LLM workload to do). Same model
    functions, same sampler, same bucketing of the growing context —
    so its token streams are comparable and its cost is honest."""

    def __init__(self, cfg: DecoderConfig, params, buckets="pow2:512",
                 attn_lanes: int = 0):
        self.cfg = cfg
        self.params = jax.tree.map(jnp.asarray, params)
        self.ladder = [b for b in parse_bucket_ladder(buckets)
                       if b <= cfg.max_seq_len] or [cfg.max_seq_len]
        # the paged engine's attn_lanes: the oracle's key axis as
        # wide as the engine's view (model.forward_full docstring)
        self.attn_lanes = int(attn_lanes)
        self._fns: Dict[int, Any] = {}

    def _fn(self, bucket: int):
        fn = self._fns.get(bucket)
        if fn is None:
            cfg = self.cfg
            lanes = self.attn_lanes
            fn = jax.jit(lambda p, t, l: cfg.forward_full(
                p, t, l, attn_lanes=lanes)[0])
            self._fns[bucket] = fn
        return fn

    def generate(self, req: GenerationRequest) -> GenerationResult:
        toks = list(int(t) for t in req.prompt)
        n0 = len(toks)
        sp = req.sampling
        out: List[int] = []
        reason = "length"
        for step in range(req.max_new_tokens):
            n = len(toks)
            bucket = bucket_for(n, self.ladder)
            if bucket is None:
                bucket = self.cfg.max_seq_len
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n] = toks
            logits = self._fn(bucket)(
                self.params, jnp.asarray(padded),
                jnp.asarray([n], np.int32))
            nxt = sample_tokens(
                logits, jnp.asarray([sp.temperature], jnp.float32),
                jnp.asarray([sp.top_k], jnp.int32),
                jnp.asarray([sp.top_p], jnp.float32),
                jnp.asarray([sp.seed], jnp.int32),
                jnp.asarray([step], jnp.int32))
            tok = int(np.asarray(nxt)[0])
            if req.eos_token is not None and tok == req.eos_token:
                reason = "eos"
                break
            out.append(tok)
            toks.append(tok)
        return GenerationResult(request_id=req.request_id,
                                prompt_len=n0, tokens=out,
                                finish_reason=reason)
