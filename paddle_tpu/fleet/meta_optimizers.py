"""Meta-optimizers: strategy-driven optimizer/program rewrites.

Analog of /root/reference/python/paddle/distributed/fleet/meta_optimizers/
(amp_optimizer.py, recompute_optimizer.py, gradient_merge_optimizer.py,
lamb/lars_optimizer.py, dgc_optimizer.py, localsgd_optimizer.py,
pipeline_optimizer.py, graph_execution_optimizer.py) and of the wrapper
optimizers in fluid/optimizer.py (GradientMergeOptimizer:4994,
RecomputeOptimizer:4518). Each wraps an inner optimizer and rewrites the
program at minimize() time; fleet's strategy compiler chains them
(strategy_compiler.py analog in fleet/__init__.py).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..mesh.compat import pcast as _pcast, shard_map as _shard_map, \
    typeof as _typeof
from ..core.backward import append_backward
from ..core.program import OpDesc, default_main_program, \
    default_startup_program
from ..optimizer.static_opt import Lamb, LarsMomentum, Momentum, Optimizer


class MetaOptimizerBase:
    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, program=None):
        return self._inner.minimize(loss, startup_program=startup_program,
                                    parameter_list=parameter_list,
                                    no_grad_set=no_grad_set,
                                    program=program)


class RecomputeOptimizer(MetaOptimizerBase):
    """optimizer.py:4518 / recompute_optimizer.py — forward segments
    between user checkpoints are rematerialized in the backward
    (executor lowers remat_segments with jax.checkpoint)."""

    def __init__(self, inner, checkpoints: List):
        super().__init__(inner)
        self._checkpoints = list(checkpoints)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, program=None):
        program = program or default_main_program()
        startup = startup_program or default_startup_program()
        params_grads = append_backward(
            loss, parameter_list, no_grad_set,
            checkpoints=self._checkpoints, program=program)
        self._inner.apply_gradients(params_grads, program, startup)
        return None, params_grads


class GradientMergeOptimizer(MetaOptimizerBase):
    """optimizer.py:4994 / gradient_merge_optimizer.py — accumulate k
    microbatch grads into persistable buffers; every k-th step a
    conditional block applies the inner optimizer on the (averaged)
    accumulation and zeroes the buffers."""

    def __init__(self, inner, k_steps: int = 1, avg: bool = True):
        super().__init__(inner)
        self.k_steps = max(1, int(k_steps))
        self.avg = avg

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, program=None):
        from ..layers.helper import LayerHelper  # late: avoid cycles
        program = program or default_main_program()
        startup = startup_program or default_startup_program()
        block = program.global_block
        params_grads = append_backward(loss, parameter_list, no_grad_set,
                                       program=program)
        if self.k_steps == 1:
            self._inner.apply_gradients(params_grads, program, startup)
            return None, params_grads

        def pvar(name, value, dtype="float32", shape=()):
            nm = program._unique_name(name)
            for prog in (program, startup):
                prog.global_block.create_var(nm, shape=shape, dtype=dtype,
                                             persistable=True,
                                             stop_gradient=True)
            startup.global_block.append_op(
                "fill_constant", inputs={}, outputs={"Out": [nm]},
                attrs={"shape": list(shape), "value": value,
                       "dtype": dtype})
            return nm

        counter = pvar("gm_step", 0.0, "int32")
        block.append_op("increment", inputs={"X": [counter]},
                        outputs={"Out": [counter]}, attrs={"step": 1})
        accum_of = {}
        for p, g in params_grads:
            acc = pvar("gm_acc_" + p.name, 0.0, p.dtype,
                       tuple(p.shape or ()))
            block.append_op("elementwise_add",
                            inputs={"X": [acc], "Y": [g.name]},
                            outputs={"Out": [acc]}, attrs={"axis": -1})
            accum_of[p.name] = acc

        k_name = pvar("gm_k", self.k_steps, "int32")
        mod = program._unique_name("gm_mod")
        block.create_var(mod, shape=(), dtype="int32", stop_gradient=True)
        block.append_op("elementwise_mod",
                        inputs={"X": [counter], "Y": [k_name]},
                        outputs={"Out": [mod]}, attrs={"axis": -1})
        zero = pvar("gm_zero", 0, "int32")
        pred = program._unique_name("gm_pred")
        block.create_var(pred, shape=(), dtype="bool", stop_gradient=True)
        block.append_op("equal", inputs={"X": [mod], "Y": [zero]},
                        outputs={"Out": [pred]})

        # true block: apply inner optimizer on (averaged) accums, zero them
        true_blk = program.create_block()
        with program.block_guard(true_blk):
            lr = self._inner._create_global_learning_rate(program, startup)
            scaled_grads = []
            for p, _ in params_grads:
                acc = accum_of[p.name]
                scaled = program._unique_name(acc + "_avg")
                block_cur = program.current_block()
                block_cur.create_var(scaled, shape=tuple(p.shape or ()),
                                     dtype=p.dtype, stop_gradient=True)
                block_cur.append_op(
                    "scale", inputs={"X": [acc]},
                    outputs={"Out": [scaled]},
                    attrs={"scale": 1.0 / self.k_steps if self.avg
                           else 1.0, "bias": 0.0})
                scaled_grads.append(scaled)
            for (p, _), sg in zip(params_grads, scaled_grads):
                self._inner._append_optimize_op(
                    program.current_block(), p,
                    program.current_block().var(sg), lr, program, startup)
            for p, _ in params_grads:  # zero the buffers
                acc = accum_of[p.name]
                program.current_block().append_op(
                    "scale", inputs={"X": [acc]}, outputs={"Out": [acc]},
                    attrs={"scale": 0.0, "bias": 0.0})
        false_blk = program.create_block()  # no-op branch

        # exports: everything the true block wrote that lives in the
        # parent (params, accums, optimizer state)
        writes = []
        for op in true_blk.ops:
            for ns in op.outputs.values():
                for n in ns:
                    if n not in writes and block.has_var(n) and \
                            n not in {s for s in scaled_grads}:
                        writes.append(n)
        block.append_op(
            "cond_block_pair",
            inputs={"Cond": [pred]},
            outputs={"Out": writes},
            attrs={"true_block": true_blk.idx, "false_block": false_blk.idx,
                   "true_outs": writes, "false_outs": writes})
        return None, params_grads


class LambMetaOptimizer(MetaOptimizerBase):
    """lamb_optimizer.py — swap the inner Adam-family optimizer for Lamb
    keeping lr/clip/regularization."""

    def __init__(self, inner, lamb_weight_decay: float = 0.01,
                 exclude_from_weight_decay: Optional[List[str]] = None):
        lamb = Lamb(learning_rate=inner._learning_rate,
                    lamb_weight_decay=lamb_weight_decay,
                    grad_clip=inner.grad_clip,
                    regularization=inner.regularization)
        super().__init__(lamb)


class LarsMetaOptimizer(MetaOptimizerBase):
    """lars_optimizer.py — swap Momentum for LarsMomentum."""

    def __init__(self, inner, lars_coeff: float = 0.001,
                 lars_weight_decay: float = 0.0005):
        momentum = getattr(inner, "_momentum", 0.9)
        lars = LarsMomentum(learning_rate=inner._learning_rate,
                            momentum=momentum, lars_coeff=lars_coeff,
                            lars_weight_decay=lars_weight_decay,
                            grad_clip=inner.grad_clip,
                            regularization=inner.regularization)
        super().__init__(lars)


def dgc_compress(g, u, v, momentum: float, sparsity: float):
    """Traced DGC step for one gradient leaf (operators/dgc_op.h):
    momentum correction u' = m*u + g, accumulation v' = v + u', top-k
    selection on |v'| via lax.top_k, selected positions leave u/v (they
    were transmitted), unselected stay as local residual.

    Returns (sparse_grad, u_out, v_out); caller psums sparse_grad on the
    dp axis — the dense-allreduce-of-encoded-sparse of the reference
    (dgc_op + allreduce) becomes one masked psum riding ICI."""
    import jax
    import jax.numpy as jnp
    u2 = momentum * u + g
    v2 = v + u2
    flat = jnp.abs(v2).ravel()
    k = max(1, int(round(flat.size * (1.0 - sparsity))))
    thresh = jax.lax.top_k(flat, k)[0][-1]
    mask = jnp.abs(v2) >= thresh
    sparse = jnp.where(mask, v2, 0.0)
    return sparse, jnp.where(mask, 0.0, u2), jnp.where(mask, 0.0, v2)


class DGCMomentumOptimizer(MetaOptimizerBase):
    """optimizer.py:1181 DGCMomentumOptimizer / dgc_optimizer.py — deep
    gradient compression: after rampup, keep only the top-k fraction of
    each grad (by magnitude), accumulate the rest locally with momentum
    correction (operators/dgc_op.*). The dense allreduce of the sparse
    residual maps to the dp-axis psum of the masked grad.

    Device path: build_spmd_step() returns a jitted dp-sharded training
    step where each device compresses its local grad (dgc_compress),
    pmeans ONLY the selected entries, and applies SGD (momentum lives
    inside the correction, exactly the dgc_op formulation). Before
    rampup_begin_step the step degrades to dense-psum momentum SGD, the
    reference's rampup behavior, selected branchlessly so the whole
    schedule stays one XLA program."""

    def __init__(self, inner, rampup_begin_step: int = 0,
                 sparsity: float = 0.999):
        super().__init__(inner)
        self._rampup = rampup_begin_step
        self._sparsity = float(sparsity)
        self._step = 0
        self._residual = {}

    def compress(self, name: str, grad: np.ndarray) -> np.ndarray:
        """Eager/host-path compression (plain residual, no momentum
        correction — the PS/geo transport hook)."""
        self._step += 1
        if self._step <= self._rampup:
            return grad
        g = np.asarray(grad) + self._residual.get(name, 0.0)
        flat = np.abs(g).ravel()
        k = max(1, int(round(flat.size * (1.0 - self._sparsity))))
        thresh = np.partition(flat, -k)[-k]
        mask = np.abs(g) >= thresh
        self._residual[name] = np.where(mask, 0.0, g)
        return np.where(mask, g, 0.0)

    def build_spmd_step(self, loss_fn, mesh, lr: float,
                        momentum: float = 0.9, axis: str = "dp"):
        """(step_fn, init_state). step_fn(params, state, batch) ->
        (params, state, loss): params/loss replicated, state carries the
        per-device u/v residuals (leading dp dim) + the step counter,
        batch is globally batched and sharded over `axis` inside.

        loss_fn(params, batch) -> scalar mean loss."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        n = mesh.shape[axis]
        sparsity, rampup = self._sparsity, self._rampup

        def body(params, uv, step, batch):
            u_tree, v_tree = uv
            squeeze = lambda t: jax.tree.map(lambda x: x[0], t)
            u_tree, v_tree = squeeze(u_tree), squeeze(v_tree)
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            ramped = step > rampup  # reference: step_id > rampup begins DGC

            def sparse_leaf(g, u, v):
                sparse, u_s, v_s = dgc_compress(g, u, v, momentum, sparsity)
                # the ONLY collective of the compressed path: everything
                # but the top-k entries is zero, so this pmean is the
                # dense-allreduce-of-sparse-encoding of the reference
                return jax.lax.pmean(sparse, axis), u_s, v_s

            def dense_leaf(g, u, v):
                # rampup: plain momentum on the dense pmean; v unused
                u_d = momentum * u + jax.lax.pmean(g, axis)
                zeros = jnp.zeros_like(v)
                if axis not in _typeof(zeros).vma:
                    zeros = _pcast(zeros, (axis,), to="varying")
                # u_d is replicated in VALUE (identical pmean'ed grads ->
                # identical momentum) but typed varying via u; pcast-by-
                # pmean keeps branch output types equal to sparse_leaf's
                return jax.lax.pmean(u_d, axis), u_d, zeros

            def leaf(g, u, v):
                if rampup <= 0:  # static: never a dense step, no
                    return sparse_leaf(g, u, v)  # dense collective at all
                return jax.lax.cond(ramped, sparse_leaf, dense_leaf,
                                    g, u, v)

            g_l, treedef = jax.tree.flatten(grads)
            res = [leaf(g, u, v) for g, u, v in zip(
                g_l, jax.tree.leaves(u_tree), jax.tree.leaves(v_tree))]
            upd = treedef.unflatten([r[0] for r in res])
            u_new = treedef.unflatten([r[1] for r in res])
            v_new = treedef.unflatten([r[2] for r in res])
            params = jax.tree.map(lambda p, d: p - lr * d, params, upd)
            loss = jax.lax.pmean(loss, axis)
            expand = lambda t: jax.tree.map(lambda x: x[None], t)
            return params, (expand(u_new), expand(v_new)), loss

        sharded = _shard_map(
            body, mesh=mesh,
            in_specs=(P(), (P(axis), P(axis)), P(), P(axis)),
            out_specs=(P(), (P(axis), P(axis)), P()))

        @jax.jit
        def step_fn(params, state, batch):
            uv, step = state
            step = step + 1
            params, uv, loss = sharded(params, uv, step, batch)
            return params, (uv, step), loss

        def init_state(params):
            zeros = lambda: jax.tree.map(
                lambda p: jnp.zeros((n,) + jnp.shape(p),
                                    jnp.result_type(p)), params)
            return (zeros(), zeros()), jnp.zeros((), jnp.int32)

        return step_fn, init_state


class LocalSGDOptimizer(MetaOptimizerBase):
    """localsgd_optimizer.py:78-140 — run k local steps, then average
    parameters across the data-parallel group.

    Device path: build_spmd_round() returns a jitted round function in
    which each dp-mesh device runs k SGD steps on its OWN divergent copy
    of the parameters (a lax.scan inside shard_map — the de-synced local
    training the reference implements with per-worker programs plus a
    snapshot/allreduce), then jax.lax.pmean re-syncs the parameters, the
    reference's communicate() allreduce over the snapshot delta."""

    def __init__(self, inner, k_steps: int = 1):
        super().__init__(inner)
        self.k_steps = k_steps

    def average_params(self, params, mesh=None, axis="dp"):
        import jax
        if mesh is None:
            return params
        from jax.sharding import PartitionSpec as P

        def avg(p):
            return _shard_map(
                lambda x: jax.lax.pmean(x, axis),
                mesh=mesh, in_specs=P(), out_specs=P())(p)
        return jax.tree.map(avg, params)

    def build_spmd_round(self, loss_fn, mesh, lr: float, axis: str = "dp"):
        """round_fn(params, batches) -> (params, mean_final_loss).
        batches: pytree of [k_steps, B_global, ...] arrays; the global
        batch dim shards over `axis`, so device d sees its own k local
        microbatches. Params enter and leave replicated (in-round copies
        diverge, pmean re-syncs). loss_fn(params, batch) -> scalar."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        k = self.k_steps

        def body(params, batches):
            def one(p, batch):
                loss, g = jax.value_and_grad(loss_fn)(p, batch)
                p = jax.tree.map(lambda a, b: a - lr * b, p, g)
                return p, loss

            p, losses = jax.lax.scan(one, params, batches)
            p = jax.tree.map(lambda x: jax.lax.pmean(x, axis), p)
            return p, jax.lax.pmean(losses[-1], axis)

        sharded = _shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(None, axis)), out_specs=(P(), P()))
        jitted = jax.jit(lambda params, batches: sharded(params, batches))

        def round_fn(params, batches):
            steps = {jnp.shape(b)[0] for b in jax.tree.leaves(batches)}
            if steps != {k}:
                raise ValueError(
                    "LocalSGD round expects k_steps=%d leading microbatch "
                    "dim, got %s" % (k, sorted(steps)))
            return jitted(params, batches)

        return round_fn
