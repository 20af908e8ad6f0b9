"""Global flags registry.

Analog of the reference's gflags surface
(/root/reference/paddle/fluid/platform/flags.cc:33-521 DEFINE_* +
pybind/global_value_getter_setter.cc exposing __set_flags/get_flags to
Python). Flags that configured CUDA allocators/streams have no TPU
meaning and are accepted as inert for script compatibility; behavioral
flags (nan/inf checking, deterministic mode, eager deletion analogs) are
read by the executor/ops.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Union

_DEFS: Dict[str, Any] = {
    # debugging (flags.cc:98 cudnn_deterministic, operator.cc:1056
    # check_nan_inf)
    "FLAGS_check_nan_inf": False,
    "FLAGS_fast_check_nan_inf": False,
    "FLAGS_cudnn_deterministic": False,
    "FLAGS_enable_unused_var_check": False,
    # memory knobs — inert on TPU (XLA owns HBM) but settable
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_allocator_strategy": "auto_growth",
    "FLAGS_eager_delete_tensor_gb": 0.0,
    "FLAGS_memory_fraction_of_eager_deletion": 1.0,
    "FLAGS_gpu_allocator_retry_time": 2000,
    # execution
    "FLAGS_benchmark": False,
    "FLAGS_paddle_num_threads": 1,
    "FLAGS_max_inplace_grad_add": 0,
    # kernels: if the Pallas flash-attention call raises, fall back to
    # the composed path (True) or propagate the error (False). Default
    # False so a broken kernel can never silently ship — the round-2
    # bench measured the fallback without anyone noticing.
    "FLAGS_flash_attention_fallback": False,
    # in-kernel hardware-PRNG flash dropout: no [B,H,Sq,Sk] keep-mask
    # in HBM. Interpret mode cannot reproduce the hardware PRNG stream,
    # so the oracle is the on-chip check in scripts/inkernel_parity.py
    # (determinism, fwd/bwd mask agreement by finite differences,
    # bias+dropout combination), which chip_smoke.py runs in its train
    # phase on every run. Speed against the HBM-mask path: not measured
    # at HEAD.
    "FLAGS_flash_inkernel_dropout": True,
    # dropout backward-residual strategy: "xla" leaves storage to XLA's
    # cost model (observed: 4 bytes/element u32 buffers), "u8" pins a
    # uint8 mask residual via custom_vjp (4x less mask HBM), "seed"
    # stores only the PRNG key and regenerates the mask in backward
    # (zero mask bytes; rbg re-run in bwd). Not measured at HEAD.
    "FLAGS_dropout_storage": "xla",
    # embedding dW strategy: True = chunked one-hot MXU matmuls instead
    # of XLA scatter-add. Decided by the round-5 end-to-end B=32 BERT
    # measurement: one-hot 204.6ms/step vs scatter 221.8ms (the scatter
    # MICRObench wins 7.9ms vs 11.0ms, but in-step the one-hot path
    # fuses into the surrounding matmul schedule better).
    "FLAGS_embedding_onehot_grad": True,
    # collectives — inert (XLA combiner thresholds are compiler flags)
    "FLAGS_fuse_parameter_memory_size": -1,
    "FLAGS_fuse_parameter_groups_size": 3,
    "FLAGS_sync_nccl_allreduce": True,
    # persistent AOT program cache (core/program_cache.py). None = auto:
    # $PADDLE_TPU_PROGRAM_CACHE_DIR if set, else the fixed directory
    # program_cache.default_dir() inside the checkout; "" disables the
    # disk cache entirely.
    "FLAGS_program_cache_dir": None,
    # in-memory Executor cache bound (entries, LRU eviction)
    "FLAGS_executor_cache_capacity": 64,
    # async dispatch pipeline (docs/async_pipeline.md): max jitted
    # steps in flight in the dataset/TrainStep loops before the host
    # waits for the oldest. 2 = classic double-buffering (host stages
    # batch N+1 while the device runs step N); 1 restores the fully
    # synchronous dispatch->fetch->dispatch loop.
    "FLAGS_executor_inflight_steps": 2,
    # train/infer_from_dataset result history: 0 keeps every batch's
    # fetches (reference behavior — unbounded host memory over a large
    # epoch), N > 0 keeps only the last N batches. The print_period /
    # fetch_handler hooks see every batch either way.
    "FLAGS_dataset_results_window": 0,
    # unified runtime telemetry (telemetry.py, docs/observability.md):
    # master gate for step-correlated trace spans, TIMER_* latency
    # histograms, and the flight recorder. Off by default — the
    # disabled fast path is one dict lookup per instrumentation site
    # (bench.py's observability block pins the overhead).
    "FLAGS_telemetry": False,
    # flight recorder depth: last N step records (step id, program key,
    # dispatch/drain timestamps, fetch sync count) kept in memory and
    # dumped into the exception notes when a step raises
    "FLAGS_telemetry_flight_steps": 64,
    # serving-grade Predictor (docs/serving.md). The bucket ladder:
    # comma-separated sizes ("1,2,4,8,16") or "pow2:N" (powers of two
    # up to N). Variable leading dims are padded UP to the nearest
    # bucket so steady-state traffic hits a small warm set of compiled
    # executables; "" disables bucketing even when a predictor asks.
    "FLAGS_predictor_shape_buckets": "pow2:128",
    # dynamic micro-batching (serving.py PredictorPool): max coalesced
    # rows per executed batch, how long the batcher waits for more
    # requests once it holds one, and the bounded request-queue depth
    # (backpressure: submit blocks, then raises ServingQueueFull)
    "FLAGS_predictor_max_batch": 32,
    "FLAGS_predictor_batch_timeout_ms": 2.0,
    "FLAGS_predictor_queue_depth": 256,
    # autoregressive generation engine (paddle_tpu/generation/,
    # docs/generation.md). The paged KV cache is a FIXED preallocated
    # pool: kv_blocks blocks of block_size tokens per layer, shared by
    # every in-flight sequence (block 0 is a reserved scratch block for
    # inactive decode lanes). decode_width is the fixed width of the
    # continuous decode batch — sequences join/leave slots without
    # changing the compiled shape.
    "FLAGS_generation_kv_blocks": 128,
    "FLAGS_generation_block_size": 16,
    "FLAGS_generation_decode_width": 8,
    # chunked prefill (PR 10, docs/generation.md "Chunked prefill"):
    # prompts stream through the SAME fixed-shape mixed step that
    # advances decode lanes, prefill_chunk prompt tokens per step
    # (at least 1: there is no other engine).
    # token_budget is the mixed batch's slot count (decode lanes +
    # prefill slots per step); 0 = auto (decode_width + prefill_chunk).
    "FLAGS_generation_prefill_chunk": 8,
    "FLAGS_generation_token_budget": 0,
    # cross-request prefix cache (PR 14, docs/generation.md "Prefix
    # caching"): chunk-aligned running-hash lookup of cached prompt
    # prefixes; hits attach the shared immutable KV blocks (refcounted,
    # copy-on-write on divergence) and start prefill at the first
    # uncached chunk. Token streams stay
    # bitwise-identical to cache-off runs — only completion ORDER can
    # change (MIGRATION.md).
    "FLAGS_generation_prefix_cache": True,
    # speculative decoding (same doc section): k > 0 lets a drafter
    # propose up to k tokens per decode lane, verified in ONE pass of
    # the mixed step (auto token_budget grows to
    # decode_width*(1+k) + prefill_chunk). Accepted streams are
    # bitwise-identical to plain decode; draft faults degrade to plain
    # decode. draft: "ngram" = host-side prompt-lookup (default, no
    # weights), "model" = a small draft decoder passed to the engine
    # ctor (draft_cfg/draft_params).
    "FLAGS_generation_spec_tokens": 0,
    "FLAGS_generation_draft": "ngram",
    # bounded request queue of the continuous-batching scheduler
    # (generation.GenerationPool): submit blocks, then raises
    # ServingQueueFull — same backpressure contract as PredictorPool
    "FLAGS_generation_queue_depth": 256,
    # mesh-native SPMD runtime (paddle_tpu/mesh/, docs/spmd.md): a mesh
    # spec string ("dp4", "dp=4,mp=2", "dp4xmp2") builds a process-wide
    # default ShardingPlan that Executor / TrainStep / hapi / Predictor
    # pick up when nothing installed one explicitly
    # (mesh.install_plan / use_plan override; "" disables). The mesh
    # topology rides in every compilation cache key and disk
    # fingerprint, NOT via lowering_snapshot — see executor.py.
    "FLAGS_mesh_spec": "",
    # live introspection server (introspect.py, docs/observability.md):
    # port for the stdlib ThreadingHTTPServer serving /metrics,
    # /healthz, /readyz, /statusz, /flightz, /programz. 0 (default) =
    # off: maybe_start() is one dict lookup and returns — zero threads,
    # zero sockets. A positive port starts the server on first
    # maybe_start() (Executor construction, pool start()); tests and
    # tooling call introspect.start(port=0) for an OS-assigned
    # ephemeral port.
    "FLAGS_introspect_port": 0,
    "FLAGS_introspect_host": "127.0.0.1",
    # request-lifecycle tracing (tracing.py, docs/observability.md):
    # per-request trace ids + monotonic stage timestamps through the
    # serving/generation pools, TTFT/TPOT + latency-decomposition
    # timers, deadline budgets, the /tracez exemplar ring. ON by
    # default — tracing is how serving explains itself; the disabled
    # path (begin() returns the shared no-op trace) is one dict lookup
    # per request and bench.py pins the enabled overhead under 1%.
    "FLAGS_request_tracing": True,
    # exemplar-ring bound: the N slowest + all errored/deadline-missed
    # requests kept with full timelines for /tracez (gauge-retracting
    # eviction, like FLAGS-less program_accounting's 512 bound)
    "FLAGS_tracing_exemplars": 32,
    # fault injection (failpoints.py, docs/robustness.md): a spec
    # string of site=action@trigger clauses joined by ";" — e.g.
    # "serving.execute=raise@once;program_cache.load=corrupt@every(2)".
    # Setting it re-arms the registry (a previously armed site absent
    # from the new spec stays armed; use "" + failpoints.disarm() to
    # clear). Disarmed sites cost ONE dict lookup — the same
    # zero-overhead contract as FLAGS_request_tracing, pinned by test.
    "FLAGS_failpoints": "",
    # SLO engine (slo.py, docs/observability.md): windowed metrics +
    # objective evaluation + burn-rate alerts + /sloz. OFF by default;
    # the disabled path (slo.evaluate returns None) is one dict lookup,
    # same contract as FLAGS_request_tracing/FLAGS_failpoints, pinned
    # by test. Enabling turns on monitor windowed aggregation with
    # FLAGS_slo_bucket_s sub-buckets x FLAGS_slo_buckets of history.
    "FLAGS_slo": False,
    "FLAGS_slo_bucket_s": 10.0,
    "FLAGS_slo_buckets": 360,
    # supervised pool recovery (serving.PredictorPool /
    # generation.GenerationPool): on a worker-loop crash the pool
    # restarts the serve loop with capped exponential backoff, failing
    # in-flight futures with a typed PoolRestarted error. max_restarts
    # bounds the total restarts before the pool goes terminally failed;
    # backoff doubles from backoff_ms and is capped at 32x.
    "FLAGS_pool_max_restarts": 3,
    "FLAGS_pool_restart_backoff_ms": 50.0,
    # gang launcher + supervisor (launch.py, docs/robustness.md
    # "Multi-host fault model"). Workers beat every interval_s; a
    # worker whose last beat is older than timeout_s is LOST (host
    # hang) and the whole gang restarts. spawn_grace_s bounds the time
    # from spawn to the FIRST beat (jax import + rendezvous ride inside
    # it). Restart budget mirrors FLAGS_pool_max_restarts: capped
    # exponential backoff from backoff_ms (doubling, capped at 32x),
    # budget refunded once a gang incarnation makes step progress,
    # sticky-terminal GangFailed on exhaustion.
    "FLAGS_launch_heartbeat_interval_s": 1.0,
    "FLAGS_launch_heartbeat_timeout_s": 10.0,
    "FLAGS_launch_spawn_grace_s": 60.0,
    "FLAGS_launch_max_restarts": 3,
    "FLAGS_launch_restart_backoff_ms": 200.0,
    # jax.distributed.initialize rendezvous bound (parallel/env.py):
    # per-attempt timeout, retry count, and backoff between attempts.
    # A rendezvous that cannot form inside the budget raises a typed
    # RendezvousTimeout instead of hanging the worker. The launcher
    # exports these to workers as PADDLE_RENDEZVOUS_* env vars.
    "FLAGS_rendezvous_timeout_s": 60.0,
    "FLAGS_rendezvous_retries": 2,
    "FLAGS_rendezvous_backoff_ms": 200.0,
    # crash-safe training (incubate/checkpoint/, docs/robustness.md):
    # N > 0 makes TrainStep.run_loop / hapi fit write an atomic
    # checkpoint (tmp+fsync+rename, manifest with step/fingerprint/mesh
    # topology) every N steps into FLAGS_checkpoint_dir and auto-resume
    # from the newest valid one on restart. 0 disables.
    "FLAGS_auto_checkpoint_steps": 0,
    "FLAGS_checkpoint_dir": "",
    # state-buffer donation in the jitted train step. Donation aliases
    # each state input to its output buffer (in-place updates, halves
    # peak param memory) but XLA:CPU runs donated executions
    # SYNCHRONOUSLY — dispatch blocks until the step completes, which
    # re-serializes the async pipeline (measured: the window=2 loop ran
    # at window=1 speed). "auto" = donate on every backend except cpu;
    # True/False force it.
    "FLAGS_executor_donate_state": "auto",
    # quantized serving (paddle_tpu/quant/, docs/quantization.md):
    # "off" (default) serves fp32 exactly as before — the quant path is
    # OPT-IN and not bitwise vs fp32. "int8" = per-channel int8 weights
    # with int8 x int8 -> int32 -> scale matmuls; "fp8" = fp8-e4m3
    # weight storage (upcast matmul) where the backend supports it.
    # Read at engine/predictor construction -> lowering flag, so fp32
    # and quantized checkpoints can never share a compiled program.
    "FLAGS_quant_mode": "off",
    # quantized KV block pool (generation/engine.py): "auto" follows
    # FLAGS_quant_mode (int8 KV when quant is on, fp32 otherwise);
    # "fp32" / "bf16" / "int8" / "fp8" pin the pool dtype ("bf16" is a
    # plain narrower pool, no scales). Quantized pools store
    # per-token-per-head absmax scales alongside and dequantize inside
    # the online-softmax loop of kernels/paged_attention.py.
    "FLAGS_generation_kv_quant": "auto",
    # adaptive kernel dispatch (paddle_tpu/autotune.py,
    # docs/autotune.md): once per (shape-bucket, backend, quant-mode)
    # key, benchmark candidate forms (kernel form x mixed-step
    # geometry), keep only candidates whose token streams are
    # bitwise-identical to the reference form, pick the winner by
    # measured step time, and persist it in the program cache's
    # policy/ sidecar. OFF by default; when on, the three geometry
    # flags below become PINS (override precedence: explicitly-set
    # flags / ctor args > persisted policy > defaults — MIGRATION.md;
    # the kernel form is pinned by the engine's `kernel=` alone):
    #   FLAGS_generation_block_size, FLAGS_generation_prefill_chunk,
    #   FLAGS_generation_token_budget
    "FLAGS_autotune": False,
    # candidate budget: how many forms one tune may trial (the
    # reference/default form is always candidate #1; the Pallas kernel
    # form is ordered last, so small budgets search geometry only)
    "FLAGS_autotune_candidates": 4,
    # probe workload scale: total generated tokens the deterministic
    # trial workload asks for (split over a handful of requests with a
    # prompt-length spread)
    "FLAGS_autotune_probe_tokens": 32,
    # quantized gradient collectives (paddle_tpu/mesh/collectives.py,
    # docs/spmd.md "Quantized collectives"): how TrainStep syncs
    # gradients over the data-parallel mesh axis.
    #   "off"  — legacy GSPMD-inserted fp32 sync (bitwise-unchanged)
    #   "fp32" — explicit per-microbatch fp32 exchange through the
    #            shard_map seam (the synchronous oracle the int8 path
    #            is budgeted against)
    #   "int8" — accumulate locally in fp32, then one block-scaled
    #            int8 ReduceScatter+AllGather of the averaged grads
    #            (PR-15 absmax scale contract; ~3.9x fewer wire bytes
    #            per exchange, NOT bitwise vs fp32)
    "FLAGS_collective_quant": "off",
    # fusion-buffer cap for the quantized exchange: big grads are
    # concatenated (reverse-topological order) into buckets of at most
    # this many MiB of fp32 payload, each exchanged as one collective
    # so XLA can overlap buckets with remaining backward compute
    "FLAGS_collective_bucket_mb": 4,
    # grads with fewer elements than this (or ndim <= 1: biases,
    # norms) skip quantization and sync per-tensor in fp32 — scale
    # overhead would eat the int8 savings and 1-D params are the most
    # error-sensitive
    "FLAGS_collective_quant_min_numel": 2048,
    # mp-axis wire for mesh-SHARDED parameters (ISSUE 19, docs/spmd.md
    # "Quantized collectives on the mp axis"): how the explicit-exchange
    # step moves model-parallel shards when FLAGS_collective_quant is on
    # and the plan's param rules shard tensors over a non-data axis.
    #   "off"  — mp-sharded plans keep the legacy GSPMD sync (the
    #            PR-17 demotion, now warned once per build and counted
    #            in STAT_collective_quant_demotions)
    #   "fp32" — compose: params stay sharded at rest, the step
    #            all-gathers them over the sharded axis in fp32 and
    #            exchanges shard gradients over the data axis (the
    #            parity oracle for the quantized wires below)
    #   "int8" — the mp all-gather moves block-scaled int8 payloads
    #            (per-SHARD scale blocks: scales are local to each
    #            rank's shard and ride the gather — never pmax'd over
    #            the axis the tensor is sharded on)
    #   "fp8"  — same wire in fp8-e4m3 (GRID_FP8=448 scale contract)
    #            where quant.supports_fp8() admits it; falls back to
    #            int8 with a one-time warning where it doesn't
    "FLAGS_collective_quant_mp": "off",
    # gang-wide observability (docs/observability.md "Gang-wide
    # observability"): host-measured per-phase step timing in TrainStep
    # (TIMER_step_phase_us{phase=stage|dispatch|compute|exchange|sync}
    # plus phase="total"). Off by default: the enabled path serializes
    # the dispatch-ahead pipeline (each step blocks to attribute time),
    # and on the manual collective path it adds a pre-exchange sync
    # fence output to the step program — hence a lowering flag
    "FLAGS_step_phases": False,
    # heartbeat-piggybacked worker metrics digest (launch.py): when on,
    # each heartbeat line carries a bounded versioned "digest" field
    # (step counter, phase-timer window stats, collective byte deltas,
    # KV occupancy). When off the wire line is byte-identical to the
    # PR-13 format and the disabled path is one flag lookup
    "FLAGS_launch_digest": True,
    # hard cap on the serialized digest JSON (bytes). Oversized digests
    # degrade (drop detail, then drop the digest entirely) worker-side;
    # the supervisor independently rejects oversized lines
    "FLAGS_launch_digest_max_bytes": 1024,
    # multi-tenant multi-model serving front door (frontdoor.py,
    # docs/frontdoor.md). OFF by default: with the flag unset nothing
    # routes through the front door, the pools serve exactly as before,
    # and the disabled check (frontdoor.active() -> None) is one module
    # global read — the same zero-overhead contract as
    # FLAGS_request_tracing/FLAGS_failpoints/FLAGS_slo, pinned by test.
    # Constructing a FrontDoor flips the flag on; close() restores it.
    "FLAGS_frontdoor": False,
    # per-endpoint admission-queue bound: past it submit() rejects
    # immediately with ServingQueueFull (the front door never blocks —
    # priority admission decides NOW, backpressure is the client's job)
    "FLAGS_frontdoor_queue_depth": 64,
    # dispatcher-thread (worker) bounds per endpoint: the autoscaler
    # grows/shrinks the live worker count inside [min, max]
    "FLAGS_frontdoor_workers_min": 1,
    "FLAGS_frontdoor_workers_max": 4,
    # autoscaler control loop: evaluation period, and the per-endpoint
    # cooldown after any scale decision (hysteresis — no flapping)
    "FLAGS_frontdoor_autoscale_interval_s": 2.0,
    "FLAGS_frontdoor_scale_cooldown_s": 10.0,
    # tenant token buckets: burst capacity = quota_rps * burst_s (a
    # tenant may spend this much headroom instantly, then refills at
    # its configured rate)
    "FLAGS_frontdoor_quota_burst_s": 2.0,
    # straggler skew score above which a rank counts as a straggler
    # (score = per-rank windowed self step-time / gang lower-median;
    # see GAUGE_gang_straggler_score in docs/observability.md)
    "FLAGS_launch_straggler_threshold": 2.0,
    # trailing window (seconds) for the supervisor's per-rank step-rate
    # / skew computation. 0 = auto: 20x the gang heartbeat interval
    "FLAGS_launch_straggler_window_s": 0.0,
}

_values: Dict[str, Any] = dict(_DEFS)

# Names the user has ever passed through set_flags(). The autotune
# override precedence (docs/autotune.md: explicit flags > persisted
# policy > defaults) needs to distinguish "the operator pinned
# FLAGS_generation_block_size" from "it still holds its default" —
# the VALUE cannot tell them apart.
_EXPLICIT: set = set()

# Flags read DURING op lowering: their value is baked into the traced
# computation, so every compilation cache key (the Executor's in-memory
# dict and the disk fingerprint) must snapshot them — flipping one
# mid-process must be a cache MISS, never a stale executable
# (ISSUE 1 satellite: FLAGS_embedding_onehot_grad / FLAGS_dropout_storage
# could previously return a pre-flip executable).
_LOWERING_FLAGS = [
    "FLAGS_check_nan_inf",
    "FLAGS_dropout_storage",
    "FLAGS_embedding_onehot_grad",
    "FLAGS_flash_attention_fallback",
    "FLAGS_flash_inkernel_dropout",
    # not read during lowering, but it changes the COMPILED executable
    # (jit donate_argnums): a mid-process flip must miss the caches
    "FLAGS_executor_donate_state",
    # quant config is baked into the traced computation (int8 matmuls,
    # KV pool dtype): a cached fp32 program must never serve a
    # quantized checkpoint, so both ride every compile key
    "FLAGS_quant_mode",
    "FLAGS_generation_kv_quant",
    # collective quantization reshapes the traced step program (bucket
    # layout, wire dtype): fp32 and quantized step programs must never
    # share an AOT entry, mirroring the qm= isolation above
    "FLAGS_collective_quant",
    "FLAGS_collective_bucket_mb",
    "FLAGS_collective_quant_min_numel",
    # the mp-axis wire mode reshapes the step program just as much:
    # gather ops, their wire dtype, and the shard-shaped grad exchange
    # are all baked into the trace
    "FLAGS_collective_quant_mp",
    # the manual-collective step program grows a pre-exchange sync
    # fence output when phase timing is on: fenced and unfenced step
    # programs must never share a compiled entry
    "FLAGS_step_phases",
]


def lowering_snapshot() -> tuple:
    """Sorted (name, value) tuple of every lowering-relevant flag —
    hashable, for use inside compilation cache keys."""
    return tuple((k, _values.get(k)) for k in sorted(_LOWERING_FLAGS))


def _canon(name: str) -> str:
    return name if name.startswith("FLAGS_") else "FLAGS_" + name


def set_flags(flags: Dict[str, Any]) -> None:
    """fluid.set_flags — unknown flags raise, like __set_flags."""
    for k, v in flags.items():
        k = _canon(k)
        if k not in _values:
            raise ValueError("unknown flag %r (known: %d flags)"
                             % (k, len(_values)))
        _values[k] = v
        _EXPLICIT.add(k)
        if k == "FLAGS_failpoints" and v:
            # arm the registry from the spec as a side effect — the
            # natural scripting surface (set_flags is how every other
            # behavior flag is driven). Lazy import: failpoints must
            # import nothing from flags at module level and vice versa.
            from paddle_tpu import failpoints as _fp
            _fp.arm_spec(v)
        elif k == "FLAGS_slo":
            # activate/deactivate the SLO engine (windowed aggregation
            # + default objectives) as a side effect, mirroring the
            # failpoints arm_spec wiring above. Lazy import for the
            # same no-cycle reason.
            from paddle_tpu import slo as _slo
            _slo._sync_from_flag(bool(v))


def get_flags(flags: Union[str, Iterable[str]]) -> Dict[str, Any]:
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for k in flags:
        ck = _canon(k)
        if ck not in _values:
            raise ValueError("unknown flag %r" % k)
        out[ck] = _values[ck]
    return out


def get_flag(name: str, default: Any = None) -> Any:
    return _values.get(_canon(name), default)


def explicitly_set(name: str) -> bool:
    """True when the flag was ever driven through set_flags() — i.e.
    the operator pinned it, as opposed to it holding its default.
    Autotune (docs/autotune.md) treats explicitly-set geometry flags
    as candidate PINS the policy may not override."""
    return _canon(name) in _EXPLICIT


def clear_explicit(*names: str) -> None:
    """Forget that the given flags (all, when none given) were
    explicitly set — test/tooling helper so a set_flags restore does
    not pin autotune forever. Values are untouched."""
    if not names:
        _EXPLICIT.clear()
        return
    for n in names:
        _EXPLICIT.discard(_canon(n))


def register_flag(name: str, default: Any, lowering: bool = False) -> None:
    _values.setdefault(_canon(name), default)
    if lowering and _canon(name) not in _LOWERING_FLAGS:
        _LOWERING_FLAGS.append(_canon(name))
