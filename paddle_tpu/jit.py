"""jit: compile eager Layers / functions into single XLA computations.

Analog of the reference's dygraph->static bridge
(/root/reference/python/paddble — dygraph/jit.py TracedLayer and
dygraph_to_static/program_translator.py:680). Where the reference re-traces
Python into a ProgramDesc via AST transforms, the TPU-native design uses
functional capture: Layer parameters/buffers are temporarily re-bound to
traced values and the eager ops execute inside a jax trace — the natural
define-by-run -> compiled path on XLA.

`functional_call` is the core primitive; `to_static` wraps inference;
`TrainStep` fuses forward+backward+optimizer into ONE donated-state jitted
step — the throughput path used by hapi Model.fit, bench.py and the
distributed trainers (reference analog: the whole
ParallelExecutor/SSA-graph machinery of framework/details/).
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .core.registry import REGISTRY, LowerCtx
from .dygraph import tape
from .dygraph.tape import Tensor
from .nn.layer import Layer


def _named_state(layer: Layer):
    """Unique (by object identity) parameter/buffer maps. Weight tying
    (e.g. BERT MLM decoder sharing the embedding matrix) yields the same
    Tensor under several names; keeping one canonical name per object
    avoids donating the same buffer twice and double-counting grads —
    setting the canonical entry updates every alias since they are the
    same Tensor object."""
    named, buffers = {}, {}
    seen = set()
    for n, t in layer.named_parameters():
        if id(t) not in seen:
            seen.add(id(t))
            named[n] = t
    for n, t in layer.named_buffers():
        if id(t) not in seen:
            seen.add(id(t))
            buffers[n] = t
    return named, buffers


def functional_call(layer: Layer, state: Dict[str, Any], *args,
                    training: bool = False, rng=None, **kwargs):
    """Run layer.forward with parameters/buffers taken from `state`
    (name -> array), returning (outputs, new_state). Pure: layer tensors
    are restored afterwards, so it is safe to call under jax tracing."""
    params, buffers = _named_state(layer)
    everything = {**params, **buffers}
    old_vals = {n: t.value for n, t in everything.items()}
    old_training = layer.training
    old_is_test = tape._state.is_test
    # raw slot, NOT the lazy property: reading .key inside a jax trace
    # would materialize PRNGKey(0) as a tracer of this trace and the
    # finally-restore below would then persist a stale tracer globally
    old_key = tape._state._key
    if rng is not None:
        tape._state.key = rng
    if training:
        layer.train()
    else:
        layer.eval()
    try:
        for n, t in everything.items():
            if n in state:
                t.value = state[n]
        with tape.no_grad():
            out = layer(*args, **kwargs)
        new_state = {n: t.value for n, t in everything.items()}
    finally:
        for n, t in everything.items():
            t.value = old_vals[n]
        layer.training = old_training
        tape._state.is_test = old_is_test
        tape._state.key = old_key
    out_vals = jax.tree.map(
        lambda x: x.value if isinstance(x, Tensor) else x, out,
        is_leaf=lambda x: isinstance(x, Tensor))
    return out_vals, new_state


def state_of(layer: Layer) -> Dict[str, Any]:
    params, buffers = _named_state(layer)
    return {n: t.value for n, t in {**params, **buffers}.items()}


def load_state(layer: Layer, state: Dict[str, Any]):
    params, buffers = _named_state(layer)
    for n, t in {**params, **buffers}.items():
        if n in state:
            t.value = state[n]


def to_static(layer_or_fn, example_inputs=None, donate_state: bool = False):
    """Compile a Layer's forward (inference) or a plain fn into one jitted
    XLA computation — TracedLayer analog (dygraph/jit.py).

    Data-dependent Python `if`/`while` in the forward are AST-converted
    to lax.cond/lax.while_loop first (dygraph_to_static module — the
    reference's ProgramTranslator pipeline), so both branches compile
    instead of the trace silently specializing or dying on a tracer
    bool."""
    import types
    from .dygraph.dygraph_to_static import (ProgramTranslator,
                                            convert_to_static)
    if isinstance(layer_or_fn, Layer):
        layer = layer_or_fn
        fwd_fn = type(layer).forward
        if ProgramTranslator.enabled:
            fwd_fn = convert_to_static(fwd_fn)

        @jax.jit
        def fwd(state, *args):
            # bind the converted forward for the duration of the trace
            # (same temporary-rebinding discipline as the params above)
            old = layer.__dict__.get("forward")
            layer.forward = types.MethodType(fwd_fn, layer)
            try:
                out, _ = functional_call(layer, state, *map(_wrap, args))
            finally:
                if old is None:
                    layer.__dict__.pop("forward", None)
                else:
                    layer.forward = old
            return out

        def run(*args):
            return fwd(state_of(layer), *[_unwrap(a) for a in args])

        run._jitted = fwd
        return run
    fn = layer_or_fn
    if ProgramTranslator.enabled:
        fn = convert_to_static(fn)
    return jax.jit(fn)


def _wrap(x):
    return Tensor(x) if not isinstance(x, Tensor) else x


def _unwrap(x):
    if x is None:  # optional model inputs (e.g. token_type_ids) pass through
        return None
    return x.value if isinstance(x, Tensor) else jnp.asarray(x)


# precomposed TIMER_step_phase_us{phase=...} keys: label composition
# costs string work per call, and the phase set is tiny and fixed
_PHASE_KEYS: Dict[str, str] = {}

# every phase the decomposition can emit, in timeline order ("total" is
# the whole-step series the others sum to; "exchange" appears only on
# the manual collective path, where the fence separates it)
STEP_PHASES = ("stage", "dispatch", "compute", "exchange", "sync",
               "total")


def _phase_timer(phase: str) -> str:
    key = _PHASE_KEYS.get(phase)
    if key is None:
        from .monitor import labeled
        key = _PHASE_KEYS[phase] = labeled("TIMER_step_phase_us",
                                           {"phase": phase})
    return key


def _accum_init(p, fill, is_scalar):
    """One optimizer-accumulator default (shared by the TrainStep
    pre-build and _opt_update's in-trace fallback so their structures
    and dtypes cannot drift)."""
    return (jnp.asarray(fill, jnp.float32) if is_scalar
            else jnp.full_like(p, fill))


def _microbatch(vals, k: int, i: int):
    """Static slice i-of-k along dim 0 of every batch leaf (None and
    scalars pass through untouched)."""
    if k == 1:
        return tuple(vals)
    out = []
    for x in vals:
        if x is None or getattr(x, "ndim", 0) == 0:
            out.append(x)
            continue
        n = int(x.shape[0])
        if n % k:
            raise ValueError(
                "grad_accum_steps=%d does not divide batch dim %d"
                % (k, n))
        mb = n // k
        out.append(jax.lax.slice_in_dim(x, i * mb, (i + 1) * mb, axis=0))
    return tuple(out)


class TrainStep:
    """One fused forward+backward+update XLA computation with donated
    parameter/optimizer state.

    Replaces the reference's per-op executor + allreduce-op-handle pipeline
    (framework/details/) for the throughput path. Optimizer updates reuse
    the optimizer op lowerings (ops/optimizers.py) applied functionally.

    loss_fn(outputs, *labels) -> scalar Tensor-valued loss computed with
    framework ops (it runs under the capture, so eager ops trace in).
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 mesh=None, batch_spec=None, param_rules=None,
                 grad_accum_steps: int = 1, amp_dtype: Optional[str] = None,
                 plan=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.param_rules = param_rules
        # mesh-native path: a ShardingPlan (or anything ShardingPlan
        # accepts — MeshSpec, "dp4xmp2", {"dp": 8}) supersedes the raw
        # mesh/param_rules pair; with nothing passed the step picks up
        # the globally installed plan (mesh.install_plan) at build time
        self.plan = plan
        self.grad_accum_steps = grad_accum_steps
        self.amp_dtype = amp_dtype
        self._step_fn = None
        self._opt_state: Dict[str, Any] = {}
        # derive the per-step rng from the seeded eager chain, NOT the
        # numpy global: paddle.seed must make a whole training run
        # reproducible (reference manual_seed contract); np.random here
        # made every TrainStep's dropout stream irreproducible
        self._rng = tape._state.next_key()
        # a restore_snapshot() on a not-yet-built step parks the arrays
        # here; __call__ applies them right after the lazy build
        self._pending_restore: Optional[Dict[str, Any]] = None
        params, buffers = _named_state(model)
        self.param_names = list(params)
        self.buffer_names = list(buffers)

    # -- functional optimizer update over the op lowerings ---------------
    def _opt_update(self, params, grads, opt_state, lr_step):
        op_type, attrs, accums = self.optimizer._eager_spec()
        opdef = REGISTRY.get(op_type)
        from .optimizer.lr_scheduler import LRScheduler
        if isinstance(self.optimizer._learning_rate, LRScheduler):
            lr = REGISTRY.get("lr_schedule").lower(
                LowerCtx(), {"Step": [lr_step]},
                self.optimizer._learning_rate._attrs())["Out"][0]
        else:
            lr = jnp.asarray(float(self.optimizer._learning_rate),
                             jnp.float32)
        pgs = list(params.items())
        gs = [grads[n] for n, _ in pgs]
        if self.optimizer.grad_clip is not None:
            with jax.named_scope("grad_clip"):
                clipped = self.optimizer.grad_clip.eager_apply(
                    list(zip([p for _, p in pgs], gs)))
            gs = [g for _, g in clipped]
        new_params, new_opt = {}, {}
        for (name, p), g in zip(pgs, gs):
            if self.optimizer.regularization is not None:
                g = self.optimizer.regularization.eager_apply(p, g)
            st = opt_state.get(name, {})
            ins = {"Param": [p], "Grad": [g.astype(p.dtype)],
                   "LearningRate": [lr]}
            nst = {}
            for in_slot, out_slot, key, fill, is_scalar in accums:
                cur = st.get(key)
                if cur is None:
                    cur = _accum_init(p, fill, is_scalar)
                ins[in_slot] = [cur]
            outs = opdef.lower(LowerCtx(), ins, attrs)
            new_params[name] = outs["ParamOut"][0]
            for in_slot, out_slot, key, fill, is_scalar in accums:
                nst[key] = outs.get(out_slot, [ins[in_slot][0]])[0]
            new_opt[name] = nst
        return new_params, new_opt

    def _make_loss_of(self, consts, rng, inputs, labels):
        """The per-microbatch loss closure differentiated by the step.
        Factored out of _build so the legacy, accumulation, and
        explicit-exchange step builders all trace the IDENTICAL
        forward+loss computation."""
        model, loss_fn = self.model, self.loss_fn

        def loss_of(p):
            # `forward` on the device trace: model and loss. jax wraps
            # the backward's operations in transpose(jvp(forward)), so
            # no scope of the program's names them (telemetry.py)
            with jax.named_scope("forward"):
                return forward_and_loss(p)

        def forward_and_loss(p):
            full = {**consts, **p}
            if self.amp_dtype is not None:
                old_amp = tape._state.amp_dtype
                tape._state.amp_dtype = self.amp_dtype
            r1, r2 = jax.random.split(rng)
            try:
                out, new_state = functional_call(
                    model, full,
                    *[Tensor(x) if x is not None else None
                      for x in inputs],
                    training=True, rng=r1)
            finally:
                if self.amp_dtype is not None:
                    tape._state.amp_dtype = old_amp
            # loss ops under an explicit rng scope so traced keys never
            # leak into the global eager chain; no_grad because
            # jax.grad differentiates
            with tape.rng_scope(r2), tape.no_grad():
                loss_t = loss_fn(
                    *(out if isinstance(out, (tuple, list))
                      else (out,)),
                    *[Tensor(x) for x in labels])
            loss_v = loss_t.value if isinstance(loss_t, Tensor) \
                else loss_t
            new_buf = {n: new_state[n] for n in self.buffer_names}
            return loss_v.astype(jnp.float32), new_buf

        return loss_of

    def _build(self, donate: bool = None):
        if donate is None:
            # same policy as the static Executor: donation is free
            # memory on TPU but serializes dispatch on XLA:CPU, which
            # would defeat run_loop/fit's dispatch-ahead window
            from .core.executor import _donate_state
            donate = _donate_state()
        from .flags import get_flag
        mode = str(get_flag("FLAGS_collective_quant"))
        k = max(1, int(self.grad_accum_steps))
        if mode != "off":
            manual = self._build_manual(mode, k, donate)
            if manual is not None:
                return manual
        # explicit-exchange path not taken: retract its gauges and
        # manifest so a legacy rebuild doesn't advertise stale bucket
        # geometry or keep bumping the byte census
        from .mesh import collectives as _coll
        _coll.retract_gauges()
        self._coll_manifest = None
        # no fence output on the GSPMD path: the compiler owns the
        # gradient sync, so exchange-wait cannot be separated from
        # device compute (docs/observability.md documents the split)
        self._has_fence = False

        def step(state, opt_state, lr_step, rng, batch):
            inputs, labels = batch
            params = {n: state[n] for n in self.param_names}
            consts = {n: state[n] for n in self.buffer_names}
            if k == 1:
                (loss, new_buf), grads = jax.value_and_grad(
                    self._make_loss_of(consts, rng, inputs, labels),
                    has_aux=True)(params)
            else:
                # grad accumulation: k static microbatches, grads
                # accumulated in fp32 and AVERAGED before _opt_update,
                # so global-norm clipping sees the accumulated gradient
                # — never a per-microbatch one
                # (tests/test_quant_collectives.py pins vs big-batch)
                rngs = jax.random.split(rng, k)
                losses, acc, new_buf = [], None, None
                for i in range(k):
                    (l, new_buf), g = jax.value_and_grad(
                        self._make_loss_of(
                            consts, rngs[i], _microbatch(inputs, k, i),
                            _microbatch(labels, k, i)),
                        has_aux=True)(params)
                    losses.append(l)
                    acc = g if acc is None else jax.tree_util.tree_map(
                        jnp.add, acc, g)
                with jax.named_scope("grad_accum"):
                    grads = jax.tree_util.tree_map(
                        lambda a: a * (1.0 / k), acc)
                    loss = jnp.mean(jnp.stack(losses))
            with jax.named_scope("optimizer"):
                new_params, new_opt = self._opt_update(
                    params, grads, opt_state, lr_step)
            new_state = {**new_buf, **new_params}
            return loss, new_state, new_opt, lr_step + 1

        jit_kwargs = {}
        if donate:
            jit_kwargs["donate_argnums"] = (0, 1)
        return jax.jit(step, **jit_kwargs)

    def _demote(self, mode: str, names, why: str):
        """Keep the legacy GSPMD sync for this build: count every
        demoted param, warn ONCE per TrainStep (a rebuild — flag flip,
        restore — must not re-fire the same diagnostic)."""
        from .monitor import stat_add
        stat_add("STAT_collective_quant_demotions", float(len(names)))
        if not getattr(self, "_warned_demotion", False):
            self._warned_demotion = True
            import warnings
            warnings.warn(
                "FLAGS_collective_quant=%r: %s — %d mesh-sharded "
                "param(s) (first: %r) keep the legacy GSPMD gradient "
                "sync; set FLAGS_collective_quant_mp to compose the "
                "quantized wire with sharded params (docs/spmd.md)"
                % (mode, why, len(names), names[0]), stacklevel=4)
        return None

    def _build_manual(self, mode: str, k: int, donate: bool):
        """Explicit-exchange step for FLAGS_collective_quant: a
        full-manual shard_map over the plan's mesh whose gradient sync
        runs through mesh/collectives.py — "fp32" exchanges every
        microbatch (the synchronous oracle), "int8" accumulates
        locally in fp32 and quantizes only the final exchange, with
        buckets staged reverse-topologically so XLA overlaps them with
        remaining backward compute.

        Mesh-sharded params (Megatron rules) COMPOSE when
        FLAGS_collective_quant_mp is on (ISSUE 19): each sharded param
        stays sharded at rest and enters the body as its local shard;
        the body all-gathers it over its sharded axis on the mp wire
        (per-SHARD scale blocks — collectives.gather_param), computes
        mp-replicated (batch shards over the data axis only, rng folds
        only the dp rank), slices each full gradient back to the local
        shard (exact: the forward is mp-replicated, so full grads are
        mp-identical and the reduce-scatter is degenerate), and runs
        the shard grads through the same bucketed dp exchange. The
        optimizer updates sharded state OUTSIDE the shard_map —
        elementwise, so GSPMD keeps every shard local.

        Returns None (caller keeps the legacy GSPMD build) when no
        plan/data axis is active, or params are mesh-sharded with
        FLAGS_collective_quant_mp off (warned once per TrainStep,
        counted in STAT_collective_quant_demotions), or a sharded spec
        is outside the single-axis evenly-divisible form the wire
        supports."""
        plan = self.plan
        if plan is None or getattr(plan, "data_axis", None) is None:
            return None
        dp_axis = plan.data_axis
        mesh = plan.mesh
        dp = int(mesh.shape[dp_axis])
        if dp <= 1:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        state0 = state_of(self.model)
        shapes = {n: tuple(np.shape(state0[n])) for n in self.param_names}
        specs = {n: plan.param_spec_tuple(n, shapes[n])
                 for n in self.param_names}
        sharded = [n for n in self.param_names
                   if any(e is not None for e in specs[n])]
        sharded_bufs = [
            n for n in self.buffer_names
            if any(e is not None for e in plan.param_spec_tuple(
                n, np.shape(state0[n])))]
        from .flags import get_flag
        from .mesh import collectives as coll
        from .mesh import compat as _compat
        mp_mode = "off"
        if sharded:
            from . import quant as _quant
            mp_raw = str(get_flag("FLAGS_collective_quant_mp"))
            if mp_raw == "off":
                return self._demote(mode,
                                    sharded, "FLAGS_collective_quant_mp "
                                    "is off")
            if sharded_bufs:
                # buffers are replicated inside the body (running
                # stats pmean over dp); a sharded buffer has no wire
                return self._demote(mode, sharded_bufs,
                                    "buffer(s) are mesh-sharded")
            mp_mode = _quant.resolve_wire_mode(mp_raw)
            axis_sizes = {str(a): int(s) for a, s in mesh.shape.items()
                          if str(a) != dp_axis}
            for n in sharded:
                try:
                    coll._local_shape(shapes[n], specs[n], axis_sizes)
                except ValueError as e:
                    return self._demote(mode, [n], str(e))
        cplan = coll.plan_buckets(
            shapes, dp_axis, dp, mode=mode,
            bucket_mb=int(get_flag("FLAGS_collective_bucket_mb")),
            min_numel=int(get_flag("FLAGS_collective_quant_min_numel")),
            specs=specs if sharded else None,
            axis_sizes={str(a): int(s) for a, s in mesh.shape.items()
                        if str(a) != dp_axis} if sharded else None,
            mp_mode=mp_mode)
        coll.publish_gauges(cplan)
        self._coll_plan = cplan
        # per-dispatch census: stat_add cannot run inside the trace, so
        # byte/op counts are derived from the plan here and bumped
        # host-side after every __call__ (ring model — monitor.py).
        # dp-axis bucket entries repeat per microbatch in fp32 mode;
        # mp-axis gather entries run ONCE per step (params are gathered
        # before the microbatch loop)
        reps = k if mode == "fp32" else 1
        fbufs = [n for n in self.buffer_names
                 if jnp.issubdtype(state0[n].dtype, jnp.floating)]
        axes: Dict[str, Dict[str, Any]] = {}
        for axis, _op, dt, nb in coll.wire_entries(cplan):
            mul = reps if axis == dp_axis else 1
            per = axes.setdefault(axis, {"ops": 0, "bytes": {}})
            per["ops"] += mul
            per["bytes"][dt] = per["bytes"].get(dt, 0) + mul * nb
        dpa = axes.setdefault(dp_axis, {"ops": 0, "bytes": {}})
        extra = coll._ring(2 * 4, dp)  # loss pmean
        for n in fbufs:
            v = state0[n]
            extra += coll._ring(2 * int(v.size) * v.dtype.itemsize, dp)
        dpa["ops"] += 1 + len(fbufs)
        dpa["bytes"]["float32"] = dpa["bytes"].get("float32", 0) + extra
        flat_bytes: Dict[str, int] = {}
        for per in axes.values():
            for dt, nb in per["bytes"].items():
                flat_bytes[dt] = flat_bytes.get(dt, 0) + nb
        self._coll_manifest = {
            "axis": dp_axis,  # the gradient-exchange axis (legacy key)
            "axes": axes,
            # all-axis aggregate: what bench/run_spmd_tests ratio reads
            "bytes": flat_bytes,
            "buckets": reps * sum(1 for b in cplan.buckets if b.quantized),
            "gathers": sum(1 for g in cplan.gathers if g.quantized),
        }
        pn, bn = self.param_names, self.buffer_names
        # step-phase fence (ISSUE 18): an extra rank-sharded (1,)
        # output depending on every PRE-exchange gradient, so the host
        # can time "local compute done" separately from "bucketed
        # exchange done". Baked into the trace -> lowering flag.
        phases = bool(get_flag("FLAGS_step_phases"))
        self._has_fence = phases

        # sharded params enter the body as their LOCAL shard and leave
        # their gradient the same way; replicated ones pass P().
        # jax accepts a dict-of-specs against a dict argument.
        param_specs = {n: P(*specs[n]) if n in set(
            g.name for g in cplan.gathers) else P()
            for n in pn}
        grad_specs = dict(param_specs)

        def step(state, opt_state, lr_step, rng, batch):
            inputs, labels = batch
            params = {n: state[n] for n in pn}
            consts = {n: state[n] for n in bn}

            def body(bparams, bconsts, brng, binputs, blabels):
                # mp composition: reassemble each sharded param's full
                # value on the quantized wire ONCE, before the
                # microbatch loop — every microbatch reuses the gather
                fparams = dict(bparams)
                for gsp in cplan.gathers:
                    fparams[gsp.name] = coll.gather_param(
                        bparams[gsp.name], gsp, cplan)
                # per-shard rng folds ONLY the dp rank: every dp rank
                # sees a different batch shard so dropout/noise streams
                # must differ, but mp ranks compute the SAME replica —
                # folding the mp rank would desynchronize the forward
                # and break the degenerate grad slice below
                r = jax.random.fold_in(brng, jax.lax.axis_index(dp_axis))
                rngs = jax.random.split(r, k)
                losses, acc, new_buf, fence = [], None, None, None
                for i in range(k):
                    (l, new_buf), g = jax.value_and_grad(
                        self._make_loss_of(
                            bconsts, rngs[i], _microbatch(binputs, k, i),
                            _microbatch(blabels, k, i)),
                        has_aux=True)(fparams)
                    losses.append(l)
                    # full grads are mp-identical (replicated forward),
                    # so each rank's shard grad is an exact local slice
                    # — the degenerate reduce-scatter, zero wire bytes
                    g = coll.shard_grads(g, cplan)
                    if phases:
                        # accumulated per microbatch so the fence stays
                        # pre-exchange even in fp32 mode, where the
                        # exchange runs inside this loop
                        with jax.named_scope("phase_fence"):
                            f = coll.phase_fence(g)
                            fence = f if fence is None else fence + f
                    if mode == "fp32":
                        # synchronous oracle: exchange EVERY microbatch
                        with jax.named_scope("grad_exchange"):
                            g = coll.exchange_grads(g, cplan)
                    acc = g if acc is None else jax.tree_util.tree_map(
                        jnp.add, acc, g)
                grads = jax.tree_util.tree_map(
                    lambda a: a * (1.0 / k), acc)
                if mode != "fp32":
                    # int8: accumulate locally in fp32, quantize only
                    # the final cross-host exchange
                    with jax.named_scope("grad_exchange"):
                        grads = coll.exchange_grads(grads, cplan)
                loss = jax.lax.pmean(jnp.mean(jnp.stack(losses)), dp_axis)
                # float buffers (running stats) are computed per-shard;
                # pmean makes the replicated out_spec well-defined
                new_buf = {
                    n: (jax.lax.pmean(v, dp_axis)
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for n, v in new_buf.items()}
                if phases:
                    return loss, grads, new_buf, fence
                return loss, grads, new_buf

            def _in_spec(prefix, vals):
                specs_ = []
                for i, x in enumerate(vals):
                    if x is None:
                        specs_.append(None)
                        continue
                    sh = plan.input_sharding("%s%d" % (prefix, i),
                                             tuple(x.shape))
                    specs_.append(sh.spec if isinstance(sh, NamedSharding)
                                  else sh)
                return tuple(specs_)

            # check_vma=False: grads leave the body replicated over dp
            # (the exchange guarantees it) but old-jax rep-tracking
            # cannot prove that through all_to_all/all_gather; nothing
            # here differentiates THROUGH the shard_map (value_and_grad
            # is inside the body), so the transpose caveat in compat.py
            # does not apply
            # the fence out_spec shards over the dp axis: pre-exchange
            # grads are rank-varying, and a replicated fence would
            # itself force the sync it is meant to observe
            out_specs = (P(), grad_specs, P(), P(dp_axis)) if phases \
                else (P(), grad_specs, P())
            synced = _compat.shard_map(
                body, mesh=mesh,
                in_specs=(param_specs, P(), P(), _in_spec("input", inputs),
                          _in_spec("label", labels)),
                out_specs=out_specs,
                check_vma=False)
            res = synced(params, consts, rng, inputs, labels)
            loss, grads, new_buf = res[0], res[1], res[2]
            with jax.named_scope("optimizer"):
                new_params, new_opt = self._opt_update(
                    params, grads, opt_state, lr_step)
            new_state = {**new_buf, **new_params}
            if phases:
                return loss, new_state, new_opt, lr_step + 1, res[3]
            return loss, new_state, new_opt, lr_step + 1

        jit_kwargs = {}
        if donate:
            jit_kwargs["donate_argnums"] = (0, 1)
        if cplan.gathers:
            # pin output shardings to the params' committed layout:
            # GSPMD spells a trailing-None spec back as its trimmed
            # twin (P('mp', None) -> P('mp',)), which is semantically
            # identical but unequal as a cache key — without the pin,
            # step 1 recompiles against step 0's outputs
            def _ns(sp):
                return NamedSharding(mesh, sp)
            state_sh = {n: _ns(param_specs[n]) for n in pn}
            state_sh.update({n: _ns(P()) for n in bn})
            _t, _a, accums = self.optimizer._eager_spec()
            opt_sh = {n: {key: _ns(P()) if is_scalar else state_sh[n]
                          for _i, _o, key, _f, is_scalar in accums}
                      for n in pn}
            outs = (_ns(P()), state_sh, opt_sh, _ns(P()))
            if phases:
                outs = outs + (_ns(P(dp_axis)),)
            jit_kwargs["out_shardings"] = outs
        return jax.jit(step, **jit_kwargs)

    def _init_opt_state(self, state):
        """Pre-build the optimizer accumulator pytree so the jitted
        step compiles ONCE: without this, call 1 compiles with an
        empty opt_state and call 2 recompiles with the populated
        structure — paying double compile time and briefly holding two
        executables' buffers (which matters on a 16G chip). Uses the
        SAME _accum_init as _opt_update's in-trace fallback, so the
        pre-built pytree cannot structurally drift from what the
        fallback would create."""
        op_type, attrs, accums = self.optimizer._eager_spec()
        del op_type, attrs

        def place_scalar(v):
            if self.mesh is not None:
                # multi-process SPMD: every jit input must be a GLOBAL
                # array over the mesh, scalars included (same treatment
                # as _lr_step)
                from jax.sharding import NamedSharding, PartitionSpec
                v = jax.device_put(np.asarray(v), NamedSharding(
                    self.mesh, PartitionSpec()))
            return v

        opt_state = {}
        for name in self.param_names:
            p = state[name]
            st = {}
            for in_slot, out_slot, key, fill, is_scalar in accums:
                # full_like inherits p's sharding, so accumulators lay
                # out exactly like their (possibly mesh-sharded) params
                v = _accum_init(p, fill, is_scalar)
                st[key] = place_scalar(v) if is_scalar else v
            opt_state[name] = st
        return opt_state

    def __call__(self, inputs, labels):
        # host spans on the profiler's clock (telemetry.py): the whole
        # call, with `stage` and `dispatch` (and the first call's
        # `build`) as its children
        from . import telemetry as _tm
        with _tm.span("pt/trainstep/call", track="dispatch"):
            return self._call(inputs, labels)

    def _call(self, inputs, labels):
        from . import telemetry as _tm
        from .failpoints import failpoint
        # kill site for crash-injection tests: BEFORE the rng split and
        # any state mutation, so a caught crash leaves the step exactly
        # as it was after the last completed call
        failpoint("trainstep.step")
        built = self._step_fn is None
        if built:
            plan = self.plan
            if plan is None and self.mesh is None and \
                    self.param_rules is None:
                from .mesh.plan import current_plan
                plan = current_plan()
            if plan is not None:
                from .mesh.plan import ShardingPlan
                if not isinstance(plan, ShardingPlan):
                    plan = ShardingPlan(plan)
                self.plan = plan
                self.mesh = plan.mesh
                if self.param_rules is None:
                    # param_sharding returns full NamedShardings; the
                    # annotate block below accepts both spellings
                    self.param_rules = \
                        lambda n, s, _p=plan: _p.param_sharding(n, s)
            with _tm.span("pt/trainstep/build", track="compile",
                          timer="TIMER_trainstep_build_us"):
                self._step_fn = self._build()
            self._state = state_of(self.model)
            self._lr_step = jnp.zeros((), jnp.int32)
            if self.mesh is not None:
                # annotate parameter shardings (tp/dp layout); GSPMD
                # propagates activation shardings + inserts collectives.
                # Without rules params replicate — and in multi-process
                # SPMD every jit input must be a GLOBAL array over the
                # mesh, scalars included
                from jax.sharding import NamedSharding, PartitionSpec as P
                rules = self.param_rules or (lambda n, s: P())

                def _psh(n, v):
                    sp = rules(n, tuple(v.shape))
                    return sp if isinstance(sp, NamedSharding) \
                        else NamedSharding(self.mesh, sp)

                self._state = {
                    n: jax.device_put(np.asarray(v), _psh(n, v))
                    for n, v in self._state.items()}
                self._lr_step = jax.device_put(
                    self._lr_step, NamedSharding(self.mesh, P()))
            if not self._opt_state:
                # AFTER the mesh device_put: full_like then inherits
                # each (possibly sharded) parameter's sharding, so the
                # accumulators lay out exactly like their params
                self._opt_state = self._init_opt_state(self._state)
        if self._pending_restore is not None:
            self._apply_restore()
        # step-phase decomposition (docs/observability.md): consecutive
        # host intervals from one clock, so the phases sum to the
        # step's wall time by construction. Off: one flag lookup.
        from .flags import get_flag
        phases_on = bool(get_flag("FLAGS_step_phases"))
        t0 = time.perf_counter() if phases_on else 0.0
        with _tm.span("pt/trainstep/stage", track="dispatch"):
            inputs = tuple(_unwrap(x) for x in (
                inputs if isinstance(inputs, (tuple, list)) else (inputs,)))
            labels = tuple(_unwrap(x) for x in (
                labels if isinstance(labels, (tuple, list)) else (labels,)))
            if self.plan is not None:
                # plan-staged batches: the input rule decides (default
                # shards dim 0 over the plan's data axis), and the
                # STAT_mesh_* instruments see the traffic
                def _stage(prefix, vals):
                    return tuple(
                        None if x is None else self.plan.place(
                            x, self.plan.input_sharding(
                                "%s%d" % (prefix, i), np.shape(x)))
                        for i, x in enumerate(vals))
                inputs = _stage("input", inputs)
                labels = _stage("label", labels)
            elif self.mesh is not None:
                # shard with THIS step's mesh — the global parallel-env mesh
                # may be a different (even differently-sized) mesh
                from .parallel.env import shard_batch
                inputs = shard_batch(inputs, mesh=self.mesh)
                labels = shard_batch(labels, mesh=self.mesh)
            self._rng, sub = jax.random.split(self._rng)
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                sub = jax.device_put(np.asarray(sub),
                                     NamedSharding(self.mesh, P()))
        step_id = None
        if _tm.enabled():
            # inherit the loop's step scope (run_loop / hapi fit) or
            # count our own calls when driven directly
            step_id = _tm.current_step()
            if step_id is None:
                self._tm_step = getattr(self, "_tm_step", 0) + 1
                step_id = self._tm_step
            _tm.flight_begin(step_id, program="trainstep:%s"
                             % type(self.model).__name__)
        # the plan is active while the step runs so trace-time mesh
        # checks (MultiHeadAttention's fused-QKV bypass, parallel/env
        # world size) see it — jax.jit traces lazily on the FIRST
        # dispatch, not in _build()
        if self.plan is not None:
            from .mesh.plan import use_plan
            plan_ctx = use_plan(self.plan)
        else:
            import contextlib
            plan_ctx = contextlib.nullcontext()
        t1 = time.perf_counter() if phases_on else 0.0
        with _tm.span("pt/trainstep/dispatch", step=step_id,
                      track="dispatch",
                      timer="TIMER_trainstep_dispatch_us"), plan_ctx:
            res = self._step_fn(self._state, self._opt_state,
                                self._lr_step, sub, (inputs, labels))
        if built:
            # once a build: the compiled step's instruction -> scope
            # table for the device trace's readers (telemetry.py). The
            # lowering and the executable are the ones the call above
            # made (jax caches both), so nothing compiles twice
            try:
                _tm.note_device_program(self._step_fn.lower(
                    res[1], res[2], res[3], sub,
                    (inputs, labels)).compile())
            except Exception:
                pass
        if getattr(self, "_has_fence", False):
            loss, self._state, self._opt_state, self._lr_step, fence = res
        else:
            loss, self._state, self._opt_state, self._lr_step = res
            fence = None
        if phases_on:
            self._observe_phases(t0, t1, loss, fence, step_id)
        m = getattr(self, "_coll_manifest", None)
        if m:
            # explicit-exchange collectives run inside the jitted step,
            # invisible to parallel/collective.py's launch counters —
            # the census is bumped per axis from the build-time wire
            # manifest (mp gather entries land on their own axis)
            from .monitor import labeled, stat_add
            for axis, per in sorted(m["axes"].items()):
                if per["ops"]:
                    stat_add("STAT_mesh_collective_%s" % axis,
                             per["ops"])
                for dt, nb in sorted(per["bytes"].items()):
                    stat_add(labeled("STAT_mesh_collective_bytes",
                                     {"axis": axis, "dtype": dt}), nb)
            if m["buckets"]:
                stat_add("STAT_collective_quant_buckets", m["buckets"])
            if m.get("gathers"):
                stat_add("STAT_collective_quant_mp_gathers",
                         m["gathers"])
        if step_id is not None:
            _tm.flight_note(step_id, "dispatched_us", _tm.now_us())
        return loss

    def _observe_phases(self, t0, t1, loss, fence, step_id):
        """Attribute the step's wall time to host phases by blocking on
        progressively later results: stage (t0->t1, host-side input
        staging + rng), dispatch (t1->return of the jitted call),
        compute (until the pre-exchange fence is ready — manual
        collective path only), exchange (fence -> new params, i.e. the
        bucketed collective + optimizer), sync (-> loss fetched). Each
        boundary is read once off one clock, so the phases sum to the
        "total" series exactly. Blocking serializes the dispatch-ahead
        pipeline, which is why FLAGS_step_phases is opt-in. On the
        legacy GSPMD path (no fence) and on XLA:CPU — where every
        output of one executable becomes ready together — the
        compute/exchange split collapses into "compute"
        (docs/observability.md states the caveat); the decomposition
        separates cleanly on a real multi-host gang."""
        t2 = time.perf_counter()
        if fence is not None:
            jax.block_until_ready(fence)
            t3 = time.perf_counter()
            jax.block_until_ready(self._state)
            t4 = time.perf_counter()
        else:
            jax.block_until_ready(self._state)
            t3 = t4 = time.perf_counter()
        jax.block_until_ready(loss)
        t5 = time.perf_counter()
        spans = [("stage", t0, t1), ("dispatch", t1, t2),
                 ("compute", t2, t3)]
        if fence is not None:
            spans.append(("exchange", t3, t4))
        spans.append(("sync", t4, t5))
        spans.append(("total", t0, t5))
        from .monitor import observe_many
        observe_many(timers=[(_phase_timer(ph), (b - a) * 1e6)
                             for ph, a, b in spans])
        from . import telemetry as _tm
        if _tm.enabled():
            # mirror the phases onto the trace so per-rank exports
            # (tools/trace_merge.py) show exchange-wait across ranks
            from . import profiler as _pf
            end_us = _tm.now_us()
            for ph, a, b in spans:
                # stage and dispatch are on the trace already, as the
                # pt/trainstep/stage and pt/trainstep/dispatch spans
                if ph in ("total", "stage", "dispatch"):
                    continue
                _pf.add_trace_event(
                    "phase/%s" % ph, end_us - (t5 - a) * 1e6,
                    (b - a) * 1e6, cat="phase", track="phase",
                    step=step_id)

    # -- crash-safe checkpointing (incubate/checkpoint/atomic.py) --------

    def state_snapshot(self) -> Dict[str, Any]:
        """Flat name->ndarray dict of the COMPLETE resume state:
        params+buffers, optimizer slots, lr step, and the host-side
        PRNG chain (each __call__ splits self._rng, so omitting it
        would fork the dropout/shuffle stream on resume — the kill-and-
        resume bitwise test fails without it). Forces a device sync (a
        checkpoint costs one barrier)."""
        if self._step_fn is None:
            raise RuntimeError(
                "TrainStep has not run yet — snapshot after at least "
                "one step (its state materializes lazily)")
        out: Dict[str, Any] = {}
        for n, v in self._state.items():
            out["state//%s" % n] = np.asarray(v)
        for pname, st in self._opt_state.items():
            for k, v in st.items():
                out["opt//%s//%s" % (pname, k)] = np.asarray(v)
        out["lr_step"] = np.asarray(self._lr_step)
        out["rng"] = np.asarray(self._rng)
        return out

    def restore_snapshot(self, arrays: Dict[str, Any]) -> None:
        """Inverse of state_snapshot. Works on a fresh TrainStep (the
        arrays are parked and applied right after the lazy build, with
        the built state's shardings) or a running one (applied now)."""
        if self._step_fn is None:
            self._pending_restore = dict(arrays)
            return
        self._pending_restore = dict(arrays)
        self._apply_restore()

    def _apply_restore(self) -> None:
        arrays = self._pending_restore
        self._pending_restore = None

        def _like(old, key):
            if key not in arrays:
                raise KeyError(
                    "checkpoint missing %r — saved from a different "
                    "model/optimizer?" % key)
            new = arrays[key]
            sh = getattr(old, "sharding", None)
            if self.mesh is not None and sh is not None:
                if jax.process_count() > 1:
                    # gang resume (launch.py): every rank restored the
                    # same host arrays; reassemble them as one global
                    # array over the multi-process mesh
                    return jax.make_array_from_process_local_data(
                        sh, np.asarray(new))
                return jax.device_put(np.asarray(new), sh)
            return jnp.asarray(new)

        self._state = {n: _like(v, "state//%s" % n)
                       for n, v in self._state.items()}
        self._opt_state = {
            pname: {k: _like(v, "opt//%s//%s" % (pname, k))
                    for k, v in st.items()}
            for pname, st in self._opt_state.items()}
        self._lr_step = _like(self._lr_step, "lr_step")
        self._rng = jnp.asarray(arrays["rng"])

    def _auto_checkpointer(self):
        """(checkpointer, every) per FLAGS_auto_checkpoint_steps /
        FLAGS_checkpoint_dir, or (None, 0) when auto-checkpointing is
        off. Shared by run_loop and hapi Model.fit."""
        from .flags import get_flag
        every = int(get_flag("FLAGS_auto_checkpoint_steps", 0) or 0)
        ckdir = str(get_flag("FLAGS_checkpoint_dir", "") or "")
        if every <= 0 or not ckdir:
            return None, 0
        from .incubate.checkpoint.atomic import AtomicCheckpointer
        return AtomicCheckpointer(ckdir), every

    def run_loop(self, batches, window: Optional[int] = None):
        """Dispatch-ahead training loop: generator over (inputs, labels)
        pairs yielding one lazy FetchHandle loss per step.

        jax dispatch is asynchronous, so each __call__ returns futures
        immediately; the loop's only job is to BOUND how far the host
        runs ahead (each in-flight step pins its feed buffers — an
        unbounded queue is unbounded memory). After dispatching step N
        the loop waits for step N-window+1 via block_until_ready — a
        readiness wait, not a transfer, so no fetch is forced to host.
        Pipelining is donation-safe: step N+1 donates the state pytree
        step N *produced*, never buffers a still-running step reads.

        window=None reads FLAGS_executor_inflight_steps (default 2);
        window=1 restores the synchronous per-step loop. hapi
        Model.fit and the pipeline bench drive their loops through the
        same discipline.

        Crash safety (docs/robustness.md): with
        FLAGS_auto_checkpoint_steps > 0 and FLAGS_checkpoint_dir set,
        the loop writes an atomic checkpoint every N steps and, on a
        fresh start, auto-resumes from the newest valid one — the first
        k batches of the (assumed deterministic) batch stream are
        consumed WITHOUT dispatch so step numbering and the data
        stream line up; skipped steps yield no handle.
        """
        from collections import deque
        from contextlib import nullcontext
        from . import telemetry as _tm
        from .core.fetch import FetchHandle
        from .flags import get_flag
        from .monitor import stat_add
        if window is None:
            window = int(get_flag("FLAGS_executor_inflight_steps", 2)
                         or 1)
        window = max(1, window)
        from .launch import heartbeat_step
        ck, ck_every = self._auto_checkpointer()
        start_step = 0
        # multi-process gang (launch.py): every rank RESTORES from the
        # shared checkpoint dir (identical state everywhere), only rank
        # 0 WRITES — the deterministic step means all ranks would write
        # identical bytes, so the extra writers are pure waste + churn
        saver = jax.process_count() == 1 or jax.process_index() == 0
        if ck is not None:
            latest = ck.load_latest()
            if latest is not None:
                start_step, arrays, _manifest = latest
                self.restore_snapshot(arrays)
                stat_add("STAT_checkpoint_resumes")
        pending: "deque" = deque()  # (step_no, FetchHandle)
        for n, (inputs, labels) in enumerate(batches, start=1):
            if n <= start_step:
                continue  # fast-forward the deterministic batch stream
            # worker.step failpoint (mid-step host-loss model) + step
            # progress into the gang heartbeat; standalone this is one
            # dict lookup and a None check
            heartbeat_step(n)
            # scope covers the FetchHandle wrap too, so the handle's
            # eventual first read syncs under this step's id
            with _tm.step_scope(n) if _tm.enabled() else nullcontext():
                handle = FetchHandle(self(inputs, labels))
            pending.append((n, handle))
            if len(pending) >= window:
                dn, h = pending.popleft()
                with _tm.span("trainstep/drain_wait", step=dn,
                              track="drain",
                              timer="TIMER_pipeline_drain_us"):
                    h.block_until_ready()
            if ck is not None and saver and n % ck_every == 0:
                # state_snapshot syncs, so the checkpoint holds step
                # n's COMPLETED state (in-flight younger steps were
                # dispatched after it and don't touch saved buffers)
                ck.save(n, self.state_snapshot())
            yield handle

    def sync_model(self):
        """Write compiled-state back into the Layer's Tensors (for eval /
        checkpointing after fit)."""
        load_state(self.model, self._state)
