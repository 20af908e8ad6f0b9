"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the three hot paths once, through the entry points a user calls,
on ONE TPU v5e chip and in ONE process:

  device    jax.devices()[0].platform must be "tpu", or the run ends here
  train     BERT-base (BertConfig() as it stands, dropout on), B=32 S=512,
            80 masked positions, Adam, bf16 AMP, through jit.TrainStep;
            the attention path traced must be flash, its dropout in-kernel
            (scripts/inkernel_parity.py runs here too: it has no CPU oracle),
            the compiled step must hold tpu_custom_call
  static    the Fluid path: the 12-layer BERT-shaped program of
            tools/check_backward_replay.py through Executor.run
  generate  GenerationEngine behind a GenerationPool at GPT-2 small's
            published widths, eight greedy requests, checked against the
            engine's own oracle (NaiveGenerator), then the same requests
            under the Pallas paged-attention kernel form
            (and the same for the looped family and the expert family at
            their benchmark configurations' widths, bfloat16)

`--chips 4` runs the mesh path instead (dp2 x mp2 TrainStep against the same
model on one of the four devices) and no other phase.

`--tiny` is the CPU rehearsal: toy sizes, no device assertion, and it never
prints the result line.

Any phase that fails ends the run at once with a non-zero exit. On success
the LAST line of stdout is one JSON object naming the device as JAX reports
it; everything else goes on earlier lines. Times printed here are
information for the reader, not a benchmark.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# sizes of the real run and of the --tiny rehearsal
REAL = dict(
    bert={},  # BertConfig() as it stands: BERT-base
    train=dict(B=32, S=512, M=80),
    static=dict(layers_n=12, H=768, FF=3072, HEADS=12, S=128, B=8),
    decoder=dict(vocab_size=50257, hidden=768, layers=12, heads=12,
                 max_seq_len=1024),  # GPT-2 small's published widths
    # the looped family at the widths of the benchmark's `ouro_2_6b`, 4
    # layers run 4 times; bfloat16 weights and KV pool
    looped=dict(vocab_size=49152, hidden_size=2048, num_hidden_layers=4,
                num_attention_heads=16, num_key_value_heads=16,
                head_dim=128, intermediate_size=5632, total_ut_steps=4,
                max_seq_len=1024),
    # the expert family at the widths of the benchmark's `k_exaone_236b`:
    # the dense layer, a window layer and a full layer, 8 of the 128
    # experts held, 64 heads over 8 key-value heads; bfloat16. Its
    # prompts outgrow the window of 128 and stay under a context cap at
    # which the oracle's full-context attention over 64 heads fits
    expert=dict(vocab_size=19200, hidden_size=6144, num_hidden_layers=3,
                num_attention_heads=64, num_key_value_heads=8,
                head_dim=128, intermediate_size=18432,
                moe_intermediate_size=2048, num_experts=128,
                num_experts_per_tok=8, sliding_windows=(128, 128, 0),
                dense_layers=1, experts_first=0, experts_held=8,
                max_seq_len=256),
    expert_prompts=(32, 192),
    engine=dict(num_blocks=1024, decode_width=8),
    prompts=(64, 512), new_tokens=32,
    mesh_bert=dict(num_hidden_layers=2, hidden_dropout_prob=0.0,
                   attention_probs_dropout_prob=0.0),
    mesh_train=dict(B=32, S=512, M=80),
)
TINY = dict(
    bert=dict(vocab_size=1000, hidden_size=128, num_hidden_layers=2,
              num_attention_heads=4, intermediate_size=256),
    train=dict(B=4, S=128, M=20),
    static=dict(layers_n=2, H=64, FF=128, HEADS=4, S=16, B=2),
    decoder=dict(vocab_size=128, hidden=64, layers=2, heads=4,
                 max_seq_len=64),
    looped=dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4, head_dim=24,
                intermediate_size=96, total_ut_steps=2, max_seq_len=64),
    expert=dict(vocab_size=128, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                intermediate_size=96, moe_intermediate_size=32,
                num_experts=16, num_experts_per_tok=4,
                sliding_windows=(8, 8, 0), dense_layers=1,
                experts_first=4, experts_held=8, max_seq_len=64),
    expert_prompts=(4, 24),
    engine=dict(num_blocks=64, decode_width=8),
    prompts=(4, 24), new_tokens=6,
    mesh_bert=dict(vocab_size=1000, hidden_size=128, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=256,
                   hidden_dropout_prob=0.0,
                   attention_probs_dropout_prob=0.0),
    mesh_train=dict(B=8, S=64, M=10),
)

# When greedy streams differ on the chip, the two candidate tokens must be
# this close under the oracle's own logits (_explain_divergence). On the v5e
# the paged engine equals its oracle token for token, but the Pallas form's
# MXU dots round fp32 scores differently from the reference form's VPU
# multiply-adds: 1 of 8 streams left the other at a 4.7e-3 gap (PR 22 chip
# run). The logits of these seeded weights are ~N(0,1) with a typical top-2
# gap of 0.2, so 5e-2 (ten times what was seen) still separates a near tie
# from a wrong answer.
LOGIT_TIE_TOL = 5e-2


def say(phase, msg):
    print("[%s] %s" % (phase, msg), flush=True)


def check(cond, phase, msg):
    """A failed check ends the run: no phase downgrades to a warning."""
    if not cond:
        say(phase, "FAILED: " + msg)
        sys.exit(1)


class CompileCounter:
    """Counts what jax itself reports: executables built or fetched on a
    jit-cache miss, persistent-cache hits, persistent-cache writes."""

    _BUILD = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _WRITE = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as m
        self.builds = self.hits = self.writes = 0
        m.register_event_duration_secs_listener(self._on_duration)
        m.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if event == self._BUILD:
            self.builds += 1

    def _on_event(self, event, **_kw):
        if event == self._HIT:
            self.hits += 1
        elif event == self._WRITE:
            self.writes += 1


def _bert_batch(cfg, B, S, M, seed):
    """The masked-LM pretraining batch of bench.py/examples/bert_pretrain.py:
    M masked slots per row, labels are the original ids there."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    pos = np.stack([rng.choice(S, M, replace=False)
                    for _ in range(B)]).astype(np.int32)
    mlm = np.take_along_axis(ids, pos, axis=1).astype(np.int32)
    nsp = rng.randint(0, 2, (B, 1)).astype(np.int32)
    return (ids, None, None, pos), (mlm, nsp)


def _lower_step(step, inputs, labels):
    """Lower the TrainStep's own jitted function on its live state, with
    the batch and key placed as TrainStep.__call__ places them — the
    executable under test, not a re-derivation of it."""
    import jax
    key = jax.random.PRNGKey(0)
    if step.mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from paddle_tpu.parallel.env import shard_batch
        batch = (shard_batch(inputs, mesh=step.mesh),
                 shard_batch(labels, mesh=step.mesh))
        key = jax.device_put(np.asarray(key),
                             NamedSharding(step.mesh, PartitionSpec()))
    else:
        batch = jax.device_put((inputs, labels))
    return step._step_fn.lower(step._state, step._opt_state, step._lr_step,
                               key, batch)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(want_chips, rehearsal):
    import importlib.metadata as md
    import jax
    import jaxlib
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    say("device", "%s jax=%s jaxlib=%s libtpu=%s python=%s"
        % (json.dumps(info), jax.__version__, jaxlib.__version__, libtpu,
           sys.version.split()[0]))
    if not rehearsal:
        check(dev.platform == "tpu", "device",
              "JAX found no accelerator (platform=%r); this script "
              "proves nothing on a CPU — use --tiny to rehearse"
              % dev.platform)
    check(info["count"] >= want_chips, "device",
          "need %d device(s), JAX reports %d" % (want_chips, info["count"]))
    # the program's own cache rule: JAX_COMPILATION_CACHE_DIR where it is
    # set, else the fixed directory inside the checkout
    from paddle_tpu.core import program_cache
    cache_dir = program_cache.resolve_dir()
    if cache_dir is not None:
        program_cache.ensure_xla_cache(cache_dir)
    say("device", "compile cache: xla=%s traces=%s"
        % (jax.config.jax_compilation_cache_dir or "off",
           cache_dir or "off"))
    return info


def phase_train(sizes, counter, on_chip):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretraining_loss)
    from paddle_tpu.nn import transformer as tr

    pt.seed(0)
    cfg = BertConfig(**sizes["bert"])
    B, S, M = (sizes["train"][k] for k in "BSM")
    model = BertForPretraining(cfg)
    opt = pt.optimizer.Adam(1e-4, parameters=model.parameters())
    step = TrainStep(model, pretraining_loss, opt,
                     amp_dtype="bfloat16" if on_chip else None)
    inputs, labels = jax.device_put(_bert_batch(cfg, B, S, M, seed=0))

    tr.reset_attention_path_log()
    fa.reset_dropout_path_log()
    losses = []
    t0 = time.perf_counter()
    losses.append(float(step(inputs, labels)))
    compile_s = time.perf_counter() - t0
    losses.append(float(step(inputs, labels)))

    builds0 = counter.builds
    timed = []
    t0 = time.perf_counter()
    for _ in range(5):
        timed.append(step(inputs, labels))
    timed = [float(x) for x in timed]  # sync: the last loss ends the window
    dt = (time.perf_counter() - t0) / 5
    losses += timed
    check(counter.builds == builds0, "train",
          "%d compilation(s) inside the 5 timed steps"
          % (counter.builds - builds0))
    check(all(np.isfinite(losses)), "train", "non-finite loss: %r" % losses)
    check(losses[-1] < losses[0], "train",
          "loss did not fall on a fixed batch: %r" % losses)

    # which paths were TRACED, read from the router's and the kernel's own
    # trace-time logs, against the router's own predicate
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    want = "flash" if tr.routes_to_flash(S, head_dim, dropout_active=True) \
        else "composed"
    paths = set(tr.attention_paths_taken())
    drops = set(fa.dropout_paths_taken())
    check(paths == {want}, "train", "router predicts %r at S=%d d=%d, "
          "traced %r" % (want, S, head_dim, sorted(paths)))
    if on_chip:
        check(want == "flash", "train", "router no longer sends the "
              "S=%d dropout step to the flash kernel" % S)
        check(drops == {"inkernel"}, "train",
              "attention dropout traced %r, not in-kernel" % sorted(drops))
        t0 = time.perf_counter()
        txt = _lower_step(step, inputs, labels).compile().as_text()
        n_calls = txt.count("tpu_custom_call")
        check(n_calls > 0, "train", "no tpu_custom_call in the compiled "
              "step: no Pallas kernel is inside it")
        mask_shape = "%d,%d,%d,%d" % (B, cfg.num_attention_heads, S, S)
        check(mask_shape not in txt, "train", "a [%s] array is in the "
              "compiled step: the score or keep-mask reached HBM"
              % mask_shape)
        say("train", "compiled step: %d tpu_custom_call, no [%s] array "
            "(text fetched in %.1fs)"
            % (n_calls, mask_shape, time.perf_counter() - t0))
        # the in-kernel PRNG path's only oracle needs the chip
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        import inkernel_parity
        fa.reset_dropout_path_log()
        inkernel_parity.check_inkernel_dropout_parity()
        check(set(fa.dropout_paths_taken()) == {"inkernel"}, "train",
              "the parity check did not trace the in-kernel path")
        say("train", "in-kernel dropout parity: determinism, fwd/bwd mask "
            "agreement, bias+dropout OK")
    say("train", "attention=%s dropout=%s losses=%s"
        % (sorted(paths), sorted(drops), ["%.4f" % x for x in losses]))
    say("train", "first step (trace+compile+run) %.1fs; step %.1f ms; "
        "%.0f tokens/s on %s (information only)"
        % (compile_s, dt * 1e3, B * S / dt,
           jax.devices()[0].device_kind))


def phase_static(sizes, counter):
    import paddle_tpu as pt
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_backward_replay as cbr

    main, startup, loss, feed = cbr.build_bert_shaped(**sizes["static"])
    rng = np.random.RandomState(0)
    feed = {k: rng.standard_normal(v.shape).astype(v.dtype)
            for k, v in feed.items()}
    exe = pt.Executor()
    exe.run(startup)
    losses, builds = [], []
    t0 = time.perf_counter()
    for _ in range(5):
        b0 = counter.builds
        out, = exe.run(main, feed=feed, fetch_list=[loss.name])
        losses.append(float(np.asarray(out).reshape(-1)[0]))
        builds.append(counter.builds - b0)
        if len(losses) == 1:
            first_s = time.perf_counter() - t0
    check(all(np.isfinite(losses)), "static", "non-finite loss: %r" % losses)
    check(len(set(losses)) == len(losses), "static",
          "loss did not move between runs: %r" % losses)
    check(not any(builds[1:]), "static",
          "compilations after the first run: %r" % builds)
    say("static", "losses=%s compiles per run=%s first run %.1fs"
        % (["%.6f" % x for x in losses], builds, first_s))


def _explain_divergence(oracle_logits, max_len, prompt, ref, got, what):
    """Greedy streams differ. Show where, and hold the difference to a
    stated tolerance: under the oracle's own full-context logits for the
    shared prefix, the two candidates must be within LOGIT_TIE_TOL (a near
    tie that reduction order may flip); anything wider is a wrong answer.
    Returns the gap's size."""
    t = next((i for i, (a, b) in enumerate(zip(ref, got)) if a != b),
             min(len(ref), len(got)))
    check(t < min(len(ref), len(got)), "generate",
          "%s: streams differ in length (%d vs %d)"
          % (what, len(ref), len(got)))
    prefix = list(prompt) + list(ref[:t])
    padded = np.zeros((1, max_len), np.int32)
    padded[0, :len(prefix)] = prefix
    logits = np.asarray(oracle_logits(
        padded, np.asarray([len(prefix)], np.int32)))[0]
    gap = float(logits[ref[t]] - logits[got[t]])
    say("generate", "NOT BITWISE: %s: first differing position %d of %d "
        "(token %d vs %d), logit gap %.3e under the oracle's logits"
        % (what, t, len(ref), ref[t], got[t], gap))
    check(abs(gap) <= LOGIT_TIE_TOL, "generate",
          "%s: logit gap %.3e exceeds the tolerance %.1e"
          % (what, gap, LOGIT_TIE_TOL))
    return abs(gap)


def phase_generate(sizes, counter, family="gpt"):
    """`family`: "gpt" (float32, generation/model.py), "looped"
    (bfloat16 weights and KV pool, generation/looped.py) or "expert"
    (bfloat16: routed experts of which a share is held, a window layer
    and a full one, grouped key-value heads; generation/moe_window.py);
    the engine, the pool, both kernel forms and the oracle are the same
    code."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.generation import (DecoderConfig, GenerationEngine,
                                       GenerationPool, GenerationRequest,
                                       NaiveGenerator, init_params, looped)
    from paddle_tpu.monitor import stat_get

    engine_kw = dict(sizes["engine"])
    if family == "gpt":
        cfg = DecoderConfig(**sizes["decoder"])
        params = init_params(cfg, seed=0)
    elif family == "looped":
        cfg = looped.LoopedDecoderConfig(**sizes["looped"])
        params = looped.init_params(cfg, seed=0, dtype=jnp.bfloat16)
        engine_kw["kv_dtype"] = "bf16"
    else:
        from paddle_tpu.generation import moe_window
        cfg = moe_window.ExpertDecoderConfig(**sizes["expert"])
        params = moe_window.init_params(cfg, seed=0, dtype=jnp.bfloat16)
        engine_kw["kv_dtype"] = "bf16"
    rng = np.random.default_rng(0)
    lo, hi = sizes["expert_prompts" if family == "expert" else "prompts"]
    new = sizes["new_tokens"]
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
               for n in np.linspace(lo, hi, 8)]

    def serve(kernel):
        engine = GenerationEngine(cfg, params, kernel=kernel, **engine_kw)
        t0 = time.perf_counter()
        engine.warmup()
        warm_s = time.perf_counter() - t0
        compiles0 = stat_get("STAT_generation_compile")
        builds0 = counter.builds
        t0 = time.perf_counter()
        with GenerationPool(engine) as pool:
            futs = [pool.submit(GenerationRequest(
                prompt=p, max_new_tokens=new, request_id=i))
                for i, p in enumerate(prompts)]
            results = [f.result(timeout=900) for f in futs]
        dt = time.perf_counter() - t0
        check(all(r.finish_reason == "length" and len(r.tokens) == new
                  for r in results), "generate",
              "%s: a request did not finish with %d tokens" % (kernel, new))
        grown = stat_get("STAT_generation_compile") - compiles0
        check(grown == 0, "generate", "%s: STAT_generation_compile grew "
              "by %d after warm-up" % (kernel, grown))
        say("generate", "%s form: 8 requests done, warm-up %.1fs, served "
            "in %.1fs, engine compiles after warm-up 0, jax executables "
            "built while serving %d, evictions %d"
            % (kernel, warm_s, dt, counter.builds - builds0,
               sum(r.evictions for r in results)))
        return [r.tokens for r in results], engine.attn_lanes

    ref_tokens, lanes = serve("reference")
    # the engine's own oracle on the shortest request: full-context
    # recompute of every token
    naive = NaiveGenerator(cfg, params, attn_lanes=lanes)
    oracle = naive.generate(GenerationRequest(
        prompt=prompts[0], max_new_tokens=new)).tokens
    # full-context logits of a padded prefix, for _explain_divergence
    full = jax.jit(lambda p, toks, n: cfg.forward_full(
        p, toks, n, attn_lanes=lanes)[0])

    def oracle_logits(toks, n):
        return full(naive.params, toks, n)
    bitwise = oracle == ref_tokens[0]
    if not bitwise:
        _explain_divergence(oracle_logits, cfg.max_seq_len, prompts[0],
                            oracle, ref_tokens[0],
                            "paged engine vs NaiveGenerator")
    say("generate", "engine vs oracle (request 0, %d-token prompt): %s"
        % (len(prompts[0]), "bitwise-equal tokens" if bitwise
           else "equal within the logit tolerance"))

    # both forms pinned in turn by `kernel=`: left free the engine takes
    # the backend's own (the kernel on a TPU, the reference form here on
    # a rehearsal's CPU)
    pallas_tokens, _ = serve("pallas")
    same = sum(a == b for a, b in zip(ref_tokens, pallas_tokens))
    gaps = [_explain_divergence(oracle_logits, cfg.max_seq_len, prompts[i],
                                a, b, "pallas vs reference form, request %d"
                                % i)
            for i, (a, b) in enumerate(zip(ref_tokens, pallas_tokens))
            if a != b]
    say("generate", "pallas form vs reference form: %d of 8 token streams "
        "equal, largest logit gap where they part %.3e (limit %.1e) on %s"
        % (same, max(gaps, default=0.0), LOGIT_TIE_TOL,
           jax.devices()[0].device_kind))


def phase_mesh(sizes, counter):
    """dp2 x mp2 over four devices against the same model and batch on one
    of them. Only --chips 4 runs this."""
    import jax
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as pt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretraining_loss)
    from paddle_tpu.parallel.env import init_parallel_env

    cfg = BertConfig(**sizes["mesh_bert"])
    B, S, M = (sizes["mesh_train"][k] for k in "BSM")
    say("mesh", "cuts: %d of 12 layers, fp32 (no AMP) at matmul precision "
        "'highest', dropout %.1f; widths H=%d FFN=%d heads=%d vocab=%d; "
        "B=%d S=%d"
        % (cfg.num_hidden_layers, cfg.hidden_dropout_prob, cfg.hidden_size,
           cfg.intermediate_size, cfg.num_attention_heads, cfg.vocab_size,
           B, S))
    inputs, labels = _bert_batch(cfg, B, S, M, seed=0)
    H, I, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size

    def rules(name, shape):
        # the Megatron layout of examples/bert_pretrain.py
        if shape == (H, I):
            return P(None, "mp")
        if shape == (I, H):
            return P("mp", None)
        if shape == (V, H):
            return P("mp", None)
        return P()

    def run(mesh):
        pt.seed(0)  # same seed, same weights on both sides
        model = BertForPretraining(cfg)
        opt = pt.optimizer.Adam(1e-4, parameters=model.parameters())
        step = TrainStep(model, pretraining_loss, opt, mesh=mesh,
                         param_rules=rules if mesh is not None else None)
        return step, [float(step(inputs, labels)) for _ in range(3)]

    # fp32 has to mean fp32 for a 1e-4 comparison: the TPU's default matmul
    # precision rounds fp32 operands to bf16, and there the one-device
    # step (Pallas layer norm) and the sharded step (composed layer norm,
    # kernels.gspmd_will_partition) round at different points — 5e-5
    # relative apart on one chip, 1e-7 at 'highest' (PR 22 chip runs)
    with jax.default_matmul_precision("highest"):
        # the one-device side first: it must not see the global mesh
        _, single = run(None)
        mesh = init_parallel_env({"dp": 2, "mp": 2},
                                 devices=jax.devices()[:4]).mesh
        step, sharded = run(mesh)
        txt = _lower_step(step, inputs, labels).compile().as_text()
    say("mesh", "one device: %s" % ["%.6f" % x for x in single])
    say("mesh", "dp2 x mp2 : %s" % ["%.6f" % x for x in sharded])
    check(all(np.isfinite(single + sharded)), "mesh", "non-finite loss")
    rel = max(abs(a - b) / abs(a) for a, b in zip(single, sharded))
    check(rel <= 1e-4, "mesh", "losses differ by %.2e relative" % rel)

    name, w = next((n, v) for n, v in step._state.items()
                   if tuple(v.shape) == (H, I))
    shards = w.addressable_shards
    devs = {s.device for s in shards}
    check(len(devs) == 4 and all(tuple(s.data.shape) == (H, I // 2)
                                 for s in shards), "mesh",
          "%s: shards %r on %d device(s), want 4 x %r"
          % (name, [tuple(s.data.shape) for s in shards], len(devs),
             (H, I // 2)))
    check("all-reduce" in txt, "mesh", "no all-reduce in the compiled step")
    say("mesh", "max relative loss gap %.2e; %s %r -> 4 shards of %r on %d "
        "devices; all-reduce in the compiled step; %d tpu_custom_call "
        "(Mosaic kernels yield to the composed path under GSPMD: "
        "kernels.gspmd_will_partition)"
        % (rel, name, tuple(w.shape), (H, I // 2), len(devs),
           txt.count("tpu_custom_call")))


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp2 x mp2 mesh phase and its "
                         "one-device comparison")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes; prints no result line")
    args = ap.parse_args(argv)
    sizes = TINY if args.tiny else REAL
    if args.tiny:
        print("REHEARSAL (--tiny): toy sizes, no device assertion, "
              "no result line", flush=True)

    counter = CompileCounter()
    t0 = time.perf_counter()
    ran = ["device"]
    info = phase_device(args.chips, rehearsal=args.tiny)
    on_chip = info["platform"] == "tpu"
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(sizes, counter))]
    else:
        phases = [("train", lambda: phase_train(sizes, counter, on_chip)),
                  ("static", lambda: phase_static(sizes, counter)),
                  ("generate", lambda: phase_generate(sizes, counter)),
                  ("generate_looped",
                   lambda: phase_generate(sizes, counter, "looped")),
                  ("generate_expert",
                   lambda: phase_generate(sizes, counter, "expert"))]
    for name, run in phases:
        t1 = time.perf_counter()
        run()
        ran.append(name)
        say(name, "passed in %.1fs" % (time.perf_counter() - t1))
    print("[cache] persistent compile cache: %d hit(s), %d entr%s written "
          "this run; %d executable(s) built or fetched; total %.1fs"
          % (counter.hits, counter.writes,
             "y" if counter.writes == 1 else "ies", counter.builds,
             time.perf_counter() - t0), flush=True)
    if args.tiny:
        print(json.dumps({"rehearsal": True, "phases": ran}), flush=True)
        return 0
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
