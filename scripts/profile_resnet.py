"""ResNet-50 MFU gap diagnosis (VERDICT r4 weak #6): the conv microbench
hits ~80% of peak but the end-to-end step measured only ~31.5% MFU, so
the loss is in glue. This script names it by timing nested subsets of
the step on the real chip:

  fwd            jitted forward only
  fwd+bwd        jax.value_and_grad, no optimizer
  full step      TrainStep (fwd+bwd+momentum update)

backward cost = (fwd+bwd) - fwd; optimizer/update cost = full - (fwd+bwd).
Each phase also reports its implied MFU so the gap attribution is direct.
A profiler trace of the full step goes to /tmp/resnet_profile for
op-level drill-down.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _bootstrap  # noqa: F401  (repo-root sys.path)
import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.jit import TrainStep, functional_call, tape, Tensor
from paddle_tpu.models.resnet import resnet50
from paddle_tpu.nn import functional as F

OUT = "/tmp/resnet_profile"
PEAK = 197e12  # bf16, v5e
FLOPS_FWD_IMG = 2 * 4.09e9


def timeit(f, n=10):
    f()  # compile
    jax.block_until_ready(f())
    t0 = time.time()
    for _ in range(n):
        r = f()
    jax.block_until_ready(r)
    return (time.time() - t0) / n


def main():
    assert jax.default_backend() == "tpu", jax.default_backend()
    pt.seed(0)
    B, HW = 256, 224
    model = resnet50(num_classes=1000)
    opt = pt.optimizer.Momentum(0.1, 0.9, parameters=model.parameters())

    def loss_fn(logits, label):
        return F.cross_entropy(logits, label, reduction="mean")

    step = TrainStep(model, loss_fn, opt, amp_dtype="bfloat16")
    rng = np.random.RandomState(0)
    x = jax.device_put(rng.randn(B, 3, HW, HW).astype(np.float32))
    y = jax.device_put(rng.randint(0, 1000, (B, 1)).astype(np.int64))

    # --- full train step
    for _ in range(2):
        float(step((x,), (y,)))
    t_full = timeit(lambda: step((x,), (y,)))

    # --- forward only / fwd+bwd on the SAME captured state + amp cast,
    # mirroring TrainStep._build's loss_of (jit.py) so the phases
    # measure exactly what the full step runs
    state = dict(step._state)
    params = {n: state[n] for n in step.param_names}
    consts = {n: state[n] for n in step.buffer_names}
    key = jax.random.PRNGKey(0)

    def fwd_loss(p, xx, yy):
        full = {**consts, **p}
        old = tape._state.amp_dtype
        tape._state.amp_dtype = "bfloat16"
        try:
            out, _ = functional_call(model, full, Tensor(xx),
                                     training=True, rng=key)
        finally:
            tape._state.amp_dtype = old
        with tape.rng_scope(key), tape.no_grad():
            lt = loss_fn(out, Tensor(yy))
        lv = lt.value if isinstance(lt, Tensor) else lt
        return lv.astype(jnp.float32)

    j_fwd = jax.jit(fwd_loss)
    t_fwd = timeit(lambda: j_fwd(params, x, y))
    j_fb = jax.jit(jax.value_and_grad(fwd_loss))
    t_fb = timeit(lambda: j_fb(params, x, y))

    def mfu(t, mult):
        return B * FLOPS_FWD_IMG * mult / t / PEAK

    print("phase timings (B=%d, %dpx, bf16):" % (B, HW))
    print("  fwd        %7.2f ms  mfu=%.3f (1x fwd flops)"
          % (t_fwd * 1e3, mfu(t_fwd, 1)))
    print("  fwd+bwd    %7.2f ms  mfu=%.3f (3x)" % (t_fb * 1e3, mfu(t_fb, 3)))
    print("  full step  %7.2f ms  mfu=%.3f (3x)  %.1f img/s"
          % (t_full * 1e3, mfu(t_full, 3), B / t_full))
    print("  -> backward = %.2f ms, optimizer/update = %.2f ms"
          % ((t_fb - t_fwd) * 1e3, (t_full - t_fb) * 1e3))

    with jax.profiler.trace(OUT):
        for _ in range(5):
            loss = step((x,), (y,))
        float(loss)
    print("trace -> %s" % OUT)


if __name__ == "__main__":
    main()
