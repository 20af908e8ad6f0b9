"""Static-graph pipeline parallelism: device_guard sections -> one SPMD
GPipe schedule.

Reference mechanics being replaced
(/root/reference/python/paddle/fluid/optimizer.py:3666 PipelineOptimizer
-> _split_program at optimizer.py:3790;
/root/reference/paddle/fluid/framework/trainer.h:207 PipelineTrainer;
/root/reference/paddle/fluid/framework/section_worker.cc:82-132): the
program (forward+backward+optimize) is split into per-device section
programs, each driven by a SectionWorker thread, with blocking queues
carrying tensors between consecutive sections and microbatches pumped
through to overlap the stages.

TPU-native design — no threads, no queues, one XLA program:

- `PipelineOptimizer.minimize` REWRITES the program: the stamped forward
  ops move into one sub-block per device_guard section and are replaced
  by a single `pipeline_train` meta-op that outputs the loss and a
  `@GRAD` var per parameter. The inner optimizer then appends its normal
  update ops against those grads, so the optimizer stage of the
  reference's pipeline collapses into the tail of the same jitted step.
- The meta-op's lowering plays the GPipe clock exactly like the dygraph
  `gpipe()` (pipeline.py): stage s = mesh position s on the `pp` axis,
  one lax.scan tick per (microbatch, stage) diagonal, lax.ppermute
  handing activations to the next stage over ICI. Sections are
  *heterogeneous* programs, so each tick `lax.switch`es into this
  device's section; inter-stage activations ride two fixed-shape packed
  buffers (f32 + i32) because an SPMD carry needs one static type while
  section boundaries have many (conv->fc pipelines change activation
  shape at every cut). The reference's queues are dynamically typed;
  packing is the static-shape price, paid once at trace time.
- The backward sections of the reference (section_worker backward
  microbatch passes) are jax.value_and_grad through the whole schedule:
  differentiating the scan+ppermute runs the communication in reverse
  automatically.

Semantics notes:
- the loss var must be a batch MEAN (the standard book-config convention):
  the schedule averages the per-microbatch losses, which equals the
  full-batch mean only for mean-reduced losses.
- persistable vars WRITTEN inside a section (BatchNorm running stats)
  are not written back to the scope — the rewrite warns. Use LayerNorm
  (or keep BN out of the pipelined middle), the same constraint the
  SPMD formulation puts on the dygraph gpipe path.
"""
from __future__ import annotations

import logging
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..mesh.compat import pcast as _pcast, shard_map as _shard_map, \
    typeof as _typeof
from .env import PP_AXIS

GRAD_SUFFIX = "@GRAD"
PIPELINE_OP = "pipeline_train"


# ---------------------------------------------------------------------------
# minimize-side program rewrite
# ---------------------------------------------------------------------------

def rewrite_pipeline_program(program, loss, num_microbatches: int,
                             axis: str = PP_AXIS, parameter_list=None):
    """Move device_guard sections into sub-blocks behind one
    `pipeline_train` meta-op; return params_grads for apply_gradients.

    Mirrors _split_program (reference optimizer.py:3790) + the
    role of PipelineTrainer section wiring, as a Program->Program
    rewrite."""
    from .pipeline import split_program_by_device
    block = program.global_block
    sections = split_program_by_device(program)
    # ops before the first device_guard (feed/data plumbing) belong to
    # stage 0 (the reference's _add_op_device_attr does the same
    # inheritance forward)
    if len(sections) > 1 and sections[0][0] is None:
        dev1, ops1 = sections[1]
        sections = [(dev1, sections[0][1] + ops1)] + list(sections[2:])
    if len(sections) < 2:
        raise ValueError(
            "pipeline requires >=2 device_guard sections; got %d "
            "(stamp the forward with fluid.device_guard)" % len(sections))
    devs = [d for d, _ in sections]
    if len(set(devs)) != len(devs):
        raise ValueError(
            "pipeline sections must be contiguous per device; got %s "
            "(interleaved device_guard blocks)" % devs)

    all_ops = [o for _, ops in sections for o in ops]
    written: set = set()
    ext: set = set()
    for o in all_ops:
        for ns in o.inputs.values():
            ext.update(n for n in ns if n not in written)
        for ns in o.outputs.values():
            written.update(ns)
    param_set = {v.name for v in program.all_parameters()}
    persist = {v.name for v in program.persistable_vars()}
    params = sorted(n for n in ext if n in param_set)
    if parameter_list is not None:
        # restrict trainable params exactly like append_backward's
        # parameter_list contract — everything else stays frozen
        keep = {p if isinstance(p, str) else p.name for p in parameter_list}
        frozen = [p for p in params if p not in keep]
        params = [p for p in params if p in keep]
    else:
        frozen = []
    feeds = sorted(n for n in ext if n not in persist)
    # frozen params still feed the sections — as non-differentiated extras
    extras = sorted([n for n in ext if n in persist and n not in param_set]
                    + frozen)
    bad_writes = sorted(n for n in written
                        if n in persist and n not in param_set)
    if bad_writes:
        logging.getLogger("paddle_tpu").warning(
            "pipeline: persistable vars written inside sections are NOT "
            "written back to the scope (per-microbatch state has no "
            "single post-step value): %s", bad_writes)

    sub_idxs = []
    for _dev, ops in sections:
        blk = program.create_block(parent_idx=block.idx)
        blk.ops.extend(ops)
        sub_idxs.append(blk.idx)
    moved = {id(o) for o in all_ops}
    block.ops = [o for o in block.ops if id(o) not in moved]

    grad_names = []
    for p in params:
        pv = block.var(p)
        if not block.has_var(p + GRAD_SUFFIX):
            block.create_var(p + GRAD_SUFFIX, shape=list(pv.shape),
                             dtype=pv.dtype, stop_gradient=True)
        grad_names.append(p + GRAD_SUFFIX)
    block.append_op(
        PIPELINE_OP,
        inputs={"Feeds": feeds, "Params": params, "Extras": extras},
        outputs={"Loss": [loss.name], "ParamGrads": grad_names},
        attrs={"sub_blocks": sub_idxs, "num_microbatches":
               int(num_microbatches), "loss": loss.name, "axis": axis,
               "devices": devs})
    return [(block.var(p), block.var(g))
            for p, g in zip(params, grad_names)]


# ---------------------------------------------------------------------------
# run-side lowering (registered in core.control_flow.LOWERINGS)
# ---------------------------------------------------------------------------

def _pick_mesh(ctx_mesh, axis: str, n_stages: int):
    from .env import get_mesh
    for mesh in (ctx_mesh, get_mesh()):
        if mesh is not None and axis in mesh.shape and \
                mesh.shape[axis] == n_stages:
            return mesh
    devs = jax.devices()
    if len(devs) < n_stages:
        raise RuntimeError(
            "pipeline_train needs %d devices on axis %r but only %d are "
            "visible and no matching global mesh exists "
            "(init_parallel_env({'%s': %d}))"
            % (n_stages, axis, len(devs), axis, n_stages))
    from jax.sharding import Mesh
    return Mesh(np.array(devs[:n_stages]), (axis,))


def _is_float(dt) -> bool:
    return jnp.issubdtype(dt, jnp.floating)


class _Layout:
    """Static packing plan for one stage boundary: which vars, at which
    flat offsets, in the f32 buffer (floats; bf16 rides losslessly as
    f32) vs the i32 buffer (ints/bools)."""

    def __init__(self, names: List[str], shapes: Dict[str, Any]):
        self.f_entries, self.i_entries = [], []
        f_off = i_off = 0
        for n in names:
            aval = shapes[n]
            size = int(np.prod(aval.shape)) if aval.shape else 1
            if _is_float(aval.dtype):
                self.f_entries.append((n, aval.shape, aval.dtype,
                                       f_off, size))
                f_off += size
            else:
                self.i_entries.append((n, aval.shape, aval.dtype,
                                       i_off, size))
                i_off += size
        self.f_size, self.i_size = f_off, i_off

    def pack(self, env: Dict[str, Any], f_total: int, i_total: int):
        fbuf = jnp.zeros((f_total,), jnp.float32)
        ibuf = jnp.zeros((i_total,), jnp.int32)
        for n, shape, dt, off, size in self.f_entries:
            fbuf = fbuf.at[off:off + size].set(
                jnp.reshape(env[n], (size,)).astype(jnp.float32))
        for n, shape, dt, off, size in self.i_entries:
            ibuf = ibuf.at[off:off + size].set(
                jnp.reshape(env[n], (size,)).astype(jnp.int32))
        return fbuf, ibuf

    def unpack(self, fbuf, ibuf) -> Dict[str, Any]:
        out = {}
        for n, shape, dt, off, size in self.f_entries:
            out[n] = jnp.reshape(fbuf[off:off + size], shape).astype(dt)
        for n, shape, dt, off, size in self.i_entries:
            out[n] = jnp.reshape(ibuf[off:off + size], shape).astype(dt)
        return out


def lower_pipeline_train(lowerer, op, env: Dict[str, Any]) -> None:
    from ..core.executor import _BlockLowerer
    from ..core.registry import LowerCtx

    program = lowerer.program
    sub_idxs = [int(i) for i in op.attr("sub_blocks")]
    n_stages = len(sub_idxs)
    n_mb = int(op.attr("num_microbatches"))
    loss_name = op.attr("loss")
    axis = op.attr("axis", PP_AXIS)
    param_names = list(op.input("Params"))
    feed_names = list(op.input("Feeds"))
    extra_names = list(op.input("Extras"))
    sections = [program.blocks[i].ops for i in sub_idxs]
    mesh = _pick_mesh(lowerer.ctx.mesh, axis, n_stages)

    # --- dataflow across stage cuts -----------------------------------
    produced_at: Dict[str, int] = {}
    last_use: Dict[str, int] = {}
    for s, ops in enumerate(sections):
        for o in ops:
            for n in o.input_names():
                if n in produced_at:
                    last_use[n] = max(last_use.get(n, -1), s)
            for n in o.output_names():
                produced_at.setdefault(n, s)
    boundaries = [sorted(n for n, ps in produced_at.items()
                         if ps <= s and last_use.get(n, -1) > s)
                  for s in range(n_stages - 1)]

    # --- microbatch feeds ---------------------------------------------
    feeds_mb_abs: Dict[str, jax.ShapeDtypeStruct] = {}
    feeds_stacked: Dict[str, Any] = {}
    mb = None
    for k in feed_names:
        v = jnp.asarray(env[k])
        if v.ndim < 1 or v.shape[0] % n_mb != 0:
            raise ValueError(
                "pipeline feed %r batch %s is not divisible by "
                "num_microbatches=%d" % (k, v.shape, n_mb))
        mb = v.shape[0] // n_mb
        feeds_stacked[k] = v.reshape((n_mb, mb) + v.shape[1:])
        feeds_mb_abs[k] = jax.ShapeDtypeStruct((mb,) + v.shape[1:], v.dtype)
    params_env = {n: jnp.asarray(env[n]) for n in param_names}
    extras_env = {n: jnp.asarray(env[n]) for n in extra_names}

    def run_section(s, env_sec, key):
        ctx2 = LowerCtx(key, is_test=lowerer.ctx.is_test, mesh=mesh)
        sub = _BlockLowerer(program, ctx2)
        env2 = dict(env_sec)
        sub.run_ops(sections[s], env2)
        return env2

    # --- boundary shapes via abstract eval of the sequential chain ----
    bnames = sorted({n for b in boundaries for n in b})

    def seq_chain(params, extras, feeds_mb, key):
        e: Dict[str, Any] = {}
        e.update(params); e.update(extras); e.update(feeds_mb)
        for s in range(n_stages):
            e = run_section(s, e, key)
        return {n: e[n] for n in bnames}

    shapes = jax.eval_shape(seq_chain, params_env, extras_env, feeds_mb_abs,
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    layouts = [_Layout(b, shapes) for b in boundaries]
    f_total = max([1] + [lo.f_size for lo in layouts])
    i_total = max([1] + [lo.i_size for lo in layouts])

    def to_vary(x):
        if axis in _typeof(x).vma:
            return x  # already device-varying on this axis
        return _pcast(x, (axis,), to="varying")

    # --- per-stage branch functions for lax.switch --------------------
    def make_branch(s):
        def branch(fbuf, ibuf, feeds_mb, params, extras, key):
            e: Dict[str, Any] = {}
            e.update(params); e.update(extras); e.update(feeds_mb)
            if s > 0:
                e.update(layouts[s - 1].unpack(fbuf, ibuf))
            e2 = run_section(s, e, key)
            if s < n_stages - 1:
                fb, ib = layouts[s].pack(e2, f_total, i_total)
            else:
                fb = jnp.zeros((f_total,), jnp.float32)
                ib = jnp.zeros((i_total,), jnp.int32)
            if s == n_stages - 1:
                loss = jnp.asarray(e2[loss_name], jnp.float32)
                loss = loss if loss.ndim == 0 else jnp.mean(loss)
            else:
                loss = jnp.zeros((), jnp.float32)
            # every branch's outputs must agree on the varying-manual-axes
            # type for lax.switch: a stage whose outputs are fresh zeros
            # (unvarying) must match one whose outputs came through the
            # device-varying buffers
            return to_vary(fb), to_vary(ib), to_vary(loss)
        return branch

    branches = [make_branch(s) for s in range(n_stages)]
    T = n_mb + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    key0 = lowerer.ctx.rng()

    def shard_body(feeds_all, params, extras, key):
        stage = jax.lax.axis_index(axis)
        # cast ALL inputs to device-varying before the scan: a branch
        # closing over a replicated (unvarying) value would get a psum
        # inserted inside the switch when transposed for the backward
        # pass, and per-device-divergent collectives deadlock — casting
        # here moves that psum to this uniform point instead
        feeds_all, params, extras, key = jax.tree.map(
            to_vary, (feeds_all, params, extras, key))
        fbuf = to_vary(jnp.zeros((f_total,), jnp.float32))
        ibuf = to_vary(jnp.zeros((i_total,), jnp.int32))
        loss0 = to_vary(jnp.zeros((), jnp.float32))

        def tick(carry, t):
            fb, ib, loss_acc = carry
            # stage s works on microbatch t - s at tick t (the GPipe
            # diagonal): feeds consumed mid-pipeline (labels at the loss
            # stage) must be sliced by THIS stage's microbatch, not the
            # entry stage's
            src = jnp.clip(t - stage, 0, n_mb - 1)
            feeds_mb = {k: v[src] for k, v in feeds_all.items()}
            key_t = jax.random.fold_in(key, t)
            # warmup/drain ticks (stage idle on the GPipe diagonal) must
            # not RUN the section at all: zero-filled boundary buffers
            # drive ops with unbounded backward at 0 (log, sqrt, div) to
            # inf, and 0-cotangent * inf = NaN would poison the psum'd
            # parameter grads (ADVICE r4). lax.cond skips the compute —
            # also saving the warmup/drain FLOPs — and passes the
            # buffers through unchanged, which downstream stages only
            # ever read on their own live ticks.
            live = jnp.logical_and(t >= stage, t - stage < n_mb)

            def run_tick(fb, ib, feeds_mb, params, extras, key_t):
                return jax.lax.switch(stage, branches, fb, ib, feeds_mb,
                                      params, extras, key_t)

            def skip_tick(fb, ib, feeds_mb, params, extras, key_t):
                # fb[0]*0: a device-varying zero (fresh constants are
                # unvarying and would mismatch the live branch's vma)
                return fb, ib, fb[0] * 0.0

            fb2, ib2, loss_mb = jax.lax.cond(
                live, run_tick, skip_tick, fb, ib, feeds_mb, params,
                extras, key_t)
            valid = jnp.logical_and(stage == n_stages - 1,
                                    t >= n_stages - 1)
            loss_acc = loss_acc + jnp.where(valid, loss_mb, 0.0)
            fb3 = jax.lax.ppermute(fb2, axis, perm)
            ib3 = jax.lax.ppermute(ib2, axis, perm)
            return (fb3, ib3, loss_acc), None

        (_, _, loss_acc), _ = jax.lax.scan(
            tick, (fbuf, ibuf, loss0), jnp.arange(T))
        return jax.lax.psum(loss_acc, axis) / n_mb

    from jax.sharding import PartitionSpec as P
    # inputs enter replicated; shard_body casts them to device-varying,
    # and the transpose of that cast psums the per-stage partial grads
    sharded = _shard_map(
        shard_body, mesh=mesh,
        in_specs=(P(), P(), P(), P()), out_specs=P())

    def pipe_loss(params):
        return sharded(feeds_stacked, params, extras_env, key0)

    loss_val, grads = jax.value_and_grad(pipe_loss)(params_env)
    env[loss_name] = loss_val
    for p in param_names:
        env[p + GRAD_SUFFIX] = grads[p]
