"""Chip experiments: bench + BERT breakdown + scatter cost (TPU only;
`--selftest` runs the imports and tiny shapes on any backend)."""
import os, time, sys
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _bootstrap  # noqa: F401  (repo-root sys.path)
import numpy as np
import jax, jax.numpy as jnp

SELFTEST = "--selftest" in sys.argv  # imports + tiny shapes, no timing

def timeit(f, *a, n=10):
    float(jnp.sum(jax.tree_util.tree_leaves(f(*a))[0].astype(jnp.float32)))
    t0=time.time()
    for _ in range(n): r=f(*a)
    float(jnp.sum(jax.tree_util.tree_leaves(r)[0].astype(jnp.float32)))
    return (time.time()-t0)/n

# 1. embedding-grad strategies at BERT scale
V, H, N = (64, 8, 16) if SELFTEST else (30522, 768, 16384)
ids = jax.device_put(np.random.randint(0, V, (N,)).astype(np.int32))
g = jnp.asarray(np.random.randn(N, H)*0.01, jnp.bfloat16)  # np has no bfloat16

@jax.jit
def scatter_grad(ids, g):
    z = jnp.zeros((V, H), jnp.float32)
    return z.at[ids].add(g.astype(jnp.float32))

@jax.jit
def onehot_grad(ids, g):
    oh = jax.nn.one_hot(ids, V, dtype=jnp.bfloat16)  # [N, V]
    return jax.lax.dot_general(oh, g, (((0,),(0,)),((),())),
                               preferred_element_type=jnp.float32)

if SELFTEST:
    # Exercise every import and jit the dW paths at tiny shapes so the
    # guard test catches broken imports/dtypes, not just syntax errors.
    float(jnp.sum(scatter_grad(ids, g)))
    float(jnp.sum(onehot_grad(ids, g)))
    from paddle_tpu.kernels.flash_attention import flash_attention
    import paddle_tpu as pt
    from paddle_tpu.ops.nn import _keep_mask
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        pretraining_loss)
    from paddle_tpu.jit import TrainStep
    pt.set_flags({"FLAGS_embedding_onehot_grad": False})
    print("tpu_experiments selftest OK")
    sys.exit(0)

print("scatter dW: %.2fms" % (timeit(scatter_grad, ids, g)*1e3))
print("one-hot dW: %.2fms" % (timeit(onehot_grad, ids, g)*1e3))

# 2. flash crossover at long S (small n to be quick)
from paddle_tpu.kernels.flash_attention import flash_attention
Hh, D = 12, 64
for S, B in [(1024, 16), (2048, 8)]:
    q = jnp.asarray(np.random.randn(B,Hh,S,D)*0.1, jnp.bfloat16)
    k = jnp.asarray(np.random.randn(B,Hh,S,D)*0.1, jnp.bfloat16)
    v = jnp.asarray(np.random.randn(B,Hh,S,D)*0.1, jnp.bfloat16)
    @jax.jit
    def ffb(q,k,v):
        def loss(q,k,v):
            return jnp.sum(flash_attention(q,k,v, sm_scale=0.125).astype(jnp.float32))
        return jax.grad(loss, argnums=(0,1,2))(q,k,v)[0]
    @jax.jit
    def cfb(q,k,v):
        def loss(q,k,v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k)*0.125
            p = jax.nn.softmax(s, axis=-1)
            return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(jnp.float32))
        return jax.grad(loss, argnums=(0,1,2))(q,k,v)[0]
    tf = timeit(ffb,q,k,v,n=5); tc = timeit(cfb,q,k,v,n=5)
    print("S=%4d: flash %.2fms composed %.2fms ratio %.2f" % (S,tf*1e3,tc*1e3,tf/tc))

# 2b. the SCORED config (S=512, dropout 0.1, padding bias): composed vs
# flash+mask-dropout vs flash+in-kernel-dropout, fwd+bwd. THIS is the
# number that decides _FLASH_MIN_SEQ (VERDICT r4 weak #2: the old sweep
# never measured the config the bench actually runs).
import paddle_tpu as pt
S, B = 512, 32
q = jnp.asarray(np.random.randn(B,Hh,S,D)*0.1, jnp.bfloat16)
k = jnp.asarray(np.random.randn(B,Hh,S,D)*0.1, jnp.bfloat16)
v = jnp.asarray(np.random.randn(B,Hh,S,D)*0.1, jnp.bfloat16)
# padded-batch mask: last ~10% keys masked, [B,1,1,S] additive
maskv = np.zeros((B,1,1,S), np.float32); maskv[..., -S//10:] = -1e9
bias = jnp.asarray(maskv, jnp.float32)
key = jax.random.PRNGKey(3)

_prior_inkernel = pt.get_flags(["FLAGS_flash_inkernel_dropout"])


def mk_flash(inkernel):
    # the flag routes at TRACE time: set it before the jit traces
    pt.set_flags({"FLAGS_flash_inkernel_dropout": inkernel})

    @jax.jit
    def f(q,k,v,bias):
        def loss(q,k,v):
            o = flash_attention(q,k,v, bias=bias, sm_scale=0.125,
                                dropout_rate=0.1, dropout_rng=key,
                                bias_needs_grad=False)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0,1,2))(q,k,v)[0]
    return f

@jax.jit
def comp(q,k,v,bias):
    def loss(q,k,v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k)*0.125 + bias
        p = jax.nn.softmax(s, axis=-1)
        from paddle_tpu.ops.nn import _keep_mask
        keep = _keep_mask(key, 0.9, p.shape)
        p = jnp.where(keep, p/0.9, 0.0)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v).astype(jnp.float32))
    return jax.grad(loss, argnums=(0,1,2))(q,k,v)[0]

t_comp = timeit(comp, q,k,v,bias, n=10)
t_fm = timeit(mk_flash(False), q,k,v,bias, n=10)
t_fi = timeit(mk_flash(True), q,k,v,bias, n=10)
print("S=512 dropout+mask f+b: composed %.2fms flash+mask %.2fms "
      "flash+inkernel %.2fms -> set _FLASH_MIN_SEQ<=512 iff a flash "
      "variant wins (after the in-kernel parity test passes)"
      % (t_comp*1e3, t_fm*1e3, t_fi*1e3))
# restore the SHIPPED default (not a hard-coded value): section 3's
# end-to-end numbers must measure the configuration users actually get
pt.set_flags(_prior_inkernel)
# NOTE: before trusting flash+inkernel, run the parity test on chip:
#   pytest tests/test_kernels.py::test_flash_inkernel_dropout_tpu -q

# 3. BERT end-to-end step sweeps. Round-5 session 1 decided the
# embedding-dW flag (one-hot won end-to-end, now the default); the open
# decisions are the dropout backward-residual strategy and whether the
# smaller memory footprint unlocks B=64 (the composed-attention mask
# buffers were the OOM cause; with flash+in-kernel they're gone and the
# FFN masks shrink 4x under "u8" / to zero under "seed").
from paddle_tpu.models.bert import BertConfig, BertForPretraining, pretraining_loss
from paddle_tpu.jit import TrainStep


def bert_step_time(B, steps=15):
    cfg = BertConfig()
    S, M = 512, 80
    model = BertForPretraining(cfg)
    opt = pt.optimizer.Adam(1e-4, parameters=model.parameters())
    step = TrainStep(model, pretraining_loss, opt, amp_dtype="bfloat16")
    rng = np.random.RandomState(0)
    ids = jax.device_put(rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))
    pos = jax.device_put(np.stack([rng.choice(S, M, replace=False) for _ in range(B)]).astype(np.int32))
    mlm = jax.device_put(np.take_along_axis(np.asarray(ids), np.asarray(pos), 1).astype(np.int32))
    nsp = jax.device_put(rng.randint(0, 2, (B, 1)).astype(np.int32))
    inputs = (ids, None, None, pos); labels = (mlm, nsp)
    for _ in range(2): float(step(inputs, labels))
    t0 = time.time()
    for _ in range(steps): loss = step(inputs, labels)
    float(loss); dt = (time.time() - t0) / steps
    Hd, L, Vv, I = 768, 12, 30522, 3072
    fl = (6*L*(4*Hd*Hd+2*Hd*I) + 12*L*Hd*S)*B*S + (6*(Hd*Hd+Hd*Vv)*M+6*(Hd*Hd+2*Hd))*B
    print("BERT B=%d: %.1fms %.0f tok/s mfu=%.3f"
          % (B, dt*1e3, B*S/dt, fl/dt/197e12))
    return dt


_prior_storage = pt.get_flags(["FLAGS_dropout_storage"])
for strat in ("xla", "u8", "seed"):
    pt.set_flags({"FLAGS_dropout_storage": strat})
    print("=== B=32 dropout_storage=%s" % strat)
    try:
        bert_step_time(32)
    except Exception as e:
        print("B=32 %s FAILED: %r" % (strat, e))
pt.set_flags(_prior_storage)

# 3b. B=64 attempt per strategy (each may OOM; that itself is the data)
for strat in ("u8", "seed"):
    pt.set_flags({"FLAGS_dropout_storage": strat})
    print("=== B=64 dropout_storage=%s" % strat)
    try:
        bert_step_time(64, steps=10)
    except Exception as e:
        print("B=64 %s FAILED: %r" % (strat, type(e).__name__))
pt.set_flags(_prior_storage)
# Decision rules: default FLAGS_dropout_storage to the fastest B=32
# strategy; if any B=64 run fits AND beats B=32 MFU, flip BENCH_BERT_B.
