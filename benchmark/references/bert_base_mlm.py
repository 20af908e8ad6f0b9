"""Plain reference of the BERT masked-LM + NSP pretraining step.

Straight `jax.numpy`, float32, every matmul under
`jax.default_matmul_precision("highest")`, no kernel, no cache, no AMP.
It imports nothing of `paddle_tpu` and is handed nothing the program made:
the weights come from `make_weights(cfg, seed)` below (the benchmark gives
the SAME arrays to the program), the batch from `benchmark/traffic.py`.

What it follows. `google-bert/bert-base-uncased` as `paddle_tpu` runs it
(`paddle_tpu/models/bert.py` over paddle's `TransformerEncoderLayer`):
post-LN encoder, exact (erf) GELU, tied MLM decoder, MLM logits only at the
masked positions, loss = mean CE over all masked positions + mean CE of NSP.
Departures from the published model, all the program's, copied here so that
both sides compute the same function, and listed in the configuration file:
  - dropout also after the FFN activation (paddle's `act_dropout` defaults
    to `dropout`; the published BERT has none there);
  - layer-norm epsilon 1e-5 inside the encoder layers (published: 1e-12
    everywhere; the embedding and MLM-head norms do use 1e-12).

Dropout. The cell trains with the published 0.1/0.1, and the program draws
its attention keep-mask from the TPU's in-kernel PRNG, which nothing outside
the kernel can regenerate. The reference therefore draws masks of its own
(`jax.random.bernoulli`, upscale-in-train): the two sides are two samples
of one random step, and every limit in `LIMITS` is set against that noise
(readings in PERF.md section 6, PR 26).

Adam is the Paddle form the program lowers (`ops/optimizers.py:_adam`):
  lr_t = lr * sqrt(1 - b2^t) / (1 - b1^t);  p -= lr_t * m / (sqrt(v) + eps).

Memory. A float32 step at B=32 S=512 does not fit beside nothing else in
16 GB in one piece, so the batch is walked in blocks of rows and the
gradient summed; the loss is a sum of per-block sums over fixed
denominators, so blocks change nothing but the order of additions.

`precision` selects the arithmetic of every matmul, forward and backward:
  "float32"  the reference proper;
  "int8"     the CONTROL: operands and incoming gradients rounded to a
             symmetric int8 grid under a per-tensor absmax scale, the step
             below bf16 that a v5e tempts with (393 TOP/s against 197);
  "fp8"      the same with float8_e4m3fn operands and float8_e5m2 gradients.
`row_weights` plants the half-batch fault (rows weighted 0 leave the mean);
`fault="attention_zeroed"` plants an encoder fault (every layer's attention
output replaced by zeros, as a kernel that wrote nothing would leave it).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# --- limits -----------------------------------------------------------------
# name -> limit: every number here is HELD by every cell whose configuration
# names this module; what `compare` returns beside them is printed as
# information. Each limit is set from chip readings (PERF.md section 6, "PR
# 26: margins of the comparison") between a lower reading, the largest that
# sound runs gave, and an upper one, the smallest that the int8 control or a
# planted fault gave.
#
# Why the losses and the worst-leaf gaps are not among them. The two sides
# draw DIFFERENT dropout masks (see above). On the chip that alone moves an
# encoder leaf's gradient norm by 5-28 % and the loss after Adam's jump by up
# to 9 %: the reference against itself with other masks reads the same as the
# program against the reference, and the int8 control reads LESS than that on
# every encoder leaf. So those numbers have no upper reading; held are the
# numbers that the masks move least and that the control or a fault moves.
LIMITS = {
    # Worst of the MLM head's two label-driven leaves (STEADY_HEAD) of
    # | ||g_prog|| - ||g_ref|| | / ||g_ref||, the first gradient as the
    # optimizer got it. The decoder's bias and the head's layer-norm bias get
    # the sum over the masked positions of what flows back from the logits;
    # at seeded weights that is set by the labels and the count of masked
    # positions, not by the encoder's output, so the dropout masks do not
    # move it: sound runs read <= 0.0015 over 49 seeds. The int8 control
    # reads 0.081 to 0.179 over 9 seeds (the layer-norm bias, fed through the
    # decoder matmul's backward); half of the batch left out reads 0.40 to
    # 0.42 over 13 (the decoder's bias: norms of incoherent sums grow by
    # sqrt 2); every layer's attention output zeroed 0.064 to 0.098 (3).
    "head_bias_grad_gap": 0.02,
    # Worst of ALL the MLM head's leaves of the same gap: beside the two
    # above, the transform's weight and bias and the layer norm's gain, whose
    # gradients are sums over the 2,560 masked positions of the ENCODER'S
    # OUTPUT against what flows back from the logits. The masks move them:
    # sound runs read <= 0.0759 over 62 seeds (<= 0.046 on 61 of them). The
    # int8 control reads 0.313 to 0.429 (9 seeds), half of the batch 0.40 to
    # 0.44 (13). The limit lies 2.5 times over the lower reading and 1.6
    # times under the upper.
    "head_grad_gap": 0.19,
    # Largest shortfall 1 - ||g_prog|| / ||g_ref|| over the encoder layers'
    # leaves: a leaf whose gradient did not arrive reads 1. Sound runs read
    # <= 0.204 over 35 seeds by leaf (median 0.10; the masks, mostly a query
    # bias or a value weight); the int8 control 0.27 to 0.375 (9), which this
    # number does NOT fail. Every layer's attention output zeroed reads 1 on
    # the q, k, v and output projections (exactly: no gradient reaches them;
    # 3 seeds at the cell's size).
    "encoder_grad_shortfall": 0.5,
    # | median over the moved leaves of ||theta_3 - theta_0||_prog /
    # ||theta_3 - theta_0||_ref  -  1 |. Adam's first updates are all but
    # sign-sized, so the median leaf's change is steady: sound runs read
    # <= 0.0545 over 62 seeds (<= 0.029 on 61); a state returned unchanged
    # (or moved double) reads 1, attention zeroed 0.157 to 0.215 (3).
    "median_update_gap": 0.15,
}
HEAD = "cls."        # the MLM head's leaves
STEADY_HEAD = ("cls.decoder_bias", "cls.layer_norm.bias")
ENCODER = "bert.encoder.layers."
GRAD_NOUGHT = 1e-3   # leaves under this share of the median grad norm are
#                      moved by Adam on round-off alone; left out of the change
FAULTS = ("attention_zeroed",)

BETA1, BETA2, EPS = 0.9, 0.999, 1e-8


# --- weights ----------------------------------------------------------------

def leaf_shapes(cfg):
    """name -> (shape, kind) in the state-dict layout the program reads
    (`paddle_tpu.jit.load_state` names); kind is normal | zeros | ones."""
    H, I, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "bert.embeddings.word_embeddings.weight": ((V, H), "normal"),
        "bert.embeddings.position_embeddings.weight":
            ((cfg["max_position_embeddings"], H), "normal"),
        "bert.embeddings.token_type_embeddings.weight":
            ((cfg["type_vocab_size"], H), "normal"),
        "bert.embeddings.layer_norm.weight": ((H,), "ones"),
        "bert.embeddings.layer_norm.bias": ((H,), "zeros"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = "bert.encoder.layers.%d." % i
        for proj in ("q_proj", "k_proj", "v_proj", "out_proj"):
            out[p + "self_attn.%s.weight" % proj] = ((H, H), "normal")
            out[p + "self_attn.%s.bias" % proj] = ((H,), "zeros")
        out[p + "linear1.weight"] = ((H, I), "normal")
        out[p + "linear1.bias"] = ((I,), "zeros")
        out[p + "linear2.weight"] = ((I, H), "normal")
        out[p + "linear2.bias"] = ((H,), "zeros")
        for n in ("norm1", "norm2"):
            out[p + n + ".weight"] = ((H,), "ones")
            out[p + n + ".bias"] = ((H,), "zeros")
    out.update({
        "bert.pooler.dense.weight": ((H, H), "normal"),
        "bert.pooler.dense.bias": ((H,), "zeros"),
        "cls.decoder_bias": ((V,), "zeros"),
        "cls.transform.weight": ((H, H), "normal"),
        "cls.transform.bias": ((H,), "zeros"),
        "cls.layer_norm.weight": ((H,), "ones"),
        "cls.layer_norm.bias": ((H,), "zeros"),
        "nsp.weight": ((H, 2), "normal"),
        "nsp.bias": ((2,), "zeros"),
    })
    return out


def make_weights(cfg, seed):
    """All weights on the device in ONE jitted call from the seed, float32
    (the type the program keeps its master weights in): N(0,
    initializer_range) matrices and embeddings, zero biases, unit norms —
    BERT's published initialisation."""
    shapes = leaf_shapes(cfg)
    std = float(cfg["initializer_range"])

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, (shape, kind)) in zip(keys, shapes.items()):
            if kind == "normal":
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
            elif kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out
    return build(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


# --- arithmetic -------------------------------------------------------------

def _quantize(x, kind):
    """x rounded to a lower precision under a per-tensor absmax scale:
    `int8` to the 255 levels of a symmetric grid, a float8 type to its own."""
    top = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if kind == "int8":
        s = top / 127.0
        return jnp.round(x / s) * s
    s = top / float(jnp.finfo(kind).max)
    return (x / s).astype(kind).astype(x.dtype) * s


# operand and incoming-gradient types of the controls' matmuls
CONTROLS = {"fp8": (jnp.float8_e4m3fn, jnp.float8_e5m2),
            "int8": ("int8", "int8")}


def _mm(precision):
    """The matmul of one precision, as an einsum. A control rounds both
    operands on the way forward and, on the way back, the incoming gradient as
    well: every matmul of the step, forward and backward, is a low-precision
    matmul with float32 accumulation."""
    def exact(spec, a, b):
        return jnp.einsum(spec, a, b, precision="highest")
    if precision == "float32":
        return exact
    q_op, q_grad = CONTROLS[precision]

    def mm(spec, a, b):
        @jax.custom_vjp
        def f(a, b):
            return exact(spec, _quantize(a, q_op), _quantize(b, q_op))

        def fwd(a, b):
            return f(a, b), (a, b)

        def bwd(res, g):
            a, b = res
            _, vjp = jax.vjp(lambda x, y: exact(spec, x, y),
                             _quantize(a, q_op), _quantize(b, q_op))
            return vjp(_quantize(g, q_grad))
        f.defvjp(fwd, bwd)
        return f(a, b)
    return mm


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _drop(x, p, key):
    if not p:
        return x
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    return jnp.where(keep, x / (1.0 - p), 0.0)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def block_loss(params, cfg, block, key, precision, denom_mlm, denom_nsp,
               fault=None):
    """This block's share of the step's loss: its MLM and NSP cross-entropy
    sums over the WHOLE batch's denominators, rows weighted by `w`."""
    ids, pos, mlm, nsp, w = block
    mm = _mm(precision)
    L, nh = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    H = cfg["hidden_size"]
    hd = H // nh
    ph = cfg["hidden_dropout_prob"]
    pa = cfg["attention_probs_dropout_prob"]
    R, S = ids.shape
    k_emb, k_layers = jax.random.split(key)
    e = "bert.embeddings."
    x = (params[e + "word_embeddings.weight"][ids]
         + params[e + "position_embeddings.weight"][jnp.arange(S)][None]
         + params[e + "token_type_embeddings.weight"][0][None, None])
    x = _drop(_ln(x, params[e + "layer_norm.weight"],
                  params[e + "layer_norm.bias"], 1e-12), ph, k_emb)

    def stack(suffix):
        return jnp.stack([params["bert.encoder.layers.%d.%s" % (i, suffix)]
                          for i in range(L)])
    names = ["self_attn.%s.%s" % (p, t)
             for p in ("q_proj", "k_proj", "v_proj", "out_proj")
             for t in ("weight", "bias")] + \
        ["linear1.weight", "linear1.bias", "linear2.weight", "linear2.bias",
         "norm1.weight", "norm1.bias", "norm2.weight", "norm2.bias"]
    stacked = {n: stack(n) for n in names}

    def layer(x, xs):
        lp, k = xs
        k1, k2, k3, k4 = jax.random.split(k, 4)

        def proj(n, v):
            return mm("rsh,hk->rsk", v, lp["self_attn.%s.weight" % n]) \
                + lp["self_attn.%s.bias" % n]
        q = proj("q_proj", x).reshape(R, S, nh, hd)
        kk = proj("k_proj", x).reshape(R, S, nh, hd)
        v = proj("v_proj", x).reshape(R, S, nh, hd)
        sc = mm("rqnd,rknd->rnqk", q, kk) / math.sqrt(hd)
        pr = _drop(jax.nn.softmax(sc, axis=-1), pa, k1)
        ctx = mm("rnqk,rknd->rqnd", pr, v).reshape(R, S, H)
        if fault == "attention_zeroed":
            ctx = jnp.zeros_like(ctx)
        x = _ln(x + _drop(proj("out_proj", ctx), ph, k2),
                lp["norm1.weight"], lp["norm1.bias"], 1e-5)
        f = _gelu(mm("rsh,hi->rsi", x, lp["linear1.weight"])
                  + lp["linear1.bias"])
        f = mm("rsi,ih->rsh", _drop(f, ph, k3), lp["linear2.weight"]) \
            + lp["linear2.bias"]
        x = _ln(x + _drop(f, ph, k4), lp["norm2.weight"], lp["norm2.bias"],
                1e-5)
        return x, None
    x, _ = jax.lax.scan(layer, x, (stacked, jax.random.split(k_layers, L)))

    pooled = jnp.tanh(mm("rh,hk->rk", x[:, 0],
                         params["bert.pooler.dense.weight"])
                      + params["bert.pooler.dense.bias"])
    nsp_logits = mm("rh,hk->rk", pooled, params["nsp.weight"]) \
        + params["nsp.bias"]
    hm = jnp.take_along_axis(x, pos[..., None], axis=1)        # [R, M, H]
    t = _gelu(mm("rmh,hk->rmk", hm, params["cls.transform.weight"])
              + params["cls.transform.bias"])
    t = _ln(t, params["cls.layer_norm.weight"],
            params["cls.layer_norm.bias"], 1e-12)
    logits = mm("rmh,vh->rmv", t,
                params["bert.embeddings.word_embeddings.weight"]) \
        + params["cls.decoder_bias"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    pick = jnp.take_along_axis(logits, mlm[..., None], axis=-1)[..., 0]
    mlm_sum = jnp.sum((lse - pick) * w[:, None])
    lse2 = jax.nn.logsumexp(nsp_logits, axis=-1)
    pick2 = jnp.take_along_axis(nsp_logits, nsp, axis=-1)[..., 0]
    nsp_sum = jnp.sum((lse2 - pick2) * w)
    return mlm_sum / denom_mlm + nsp_sum / denom_nsp


def _norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for n, v in tree.items()}


class Reference:
    """Three (or `steps`) Adam steps on one fixed batch, in blocks of rows."""

    def __init__(self, cfg, lr, rows_per_block, precision="float32",
                 fault=None):
        if fault is not None and fault not in FAULTS:
            raise ValueError("unknown fault %r" % (fault,))
        self.cfg = dict(cfg)
        self.lr = float(lr)
        self.rows = int(rows_per_block)
        self.precision = precision
        cfg_t = tuple(sorted((k, v) for k, v in self.cfg.items()
                             if isinstance(v, (int, float))))

        @functools.partial(jax.jit, donate_argnums=(1,))
        def accumulate(params, acc, block, key, denoms):
            loss, g = jax.value_and_grad(block_loss)(
                params, dict(cfg_t), block, key, precision, denoms[0],
                denoms[1], fault)
            return loss, jax.tree.map(jnp.add, acc, g)

        @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
        def adam(params, grads, m, v, t):
            lr_t = self.lr * jnp.sqrt(1 - BETA2 ** t) / (1 - BETA1 ** t)
            m = jax.tree.map(lambda a, g: BETA1 * a + (1 - BETA1) * g, m,
                             grads)
            v = jax.tree.map(lambda a, g: BETA2 * a + (1 - BETA2) * g * g,
                             v, grads)
            params = jax.tree.map(
                lambda p, a, b: p - lr_t * a / (jnp.sqrt(b) + EPS),
                params, m, v)
            return params, m, v
        self._accumulate, self._adam = accumulate, adam
        self._norms = jax.jit(_norms)
        self._delta = jax.jit(lambda a, b: _norms(
            {n: a[n] - b[n] for n in a}))

    def run(self, weights, batch, seed, steps=3, row_weights=None):
        """-> dict(loss=[...], grad_norm={leaf: float}, update_norm={...}).
        `weights` is not consumed (a copy is trained)."""
        ids, pos, mlm, nsp = (jnp.asarray(x) for x in batch)
        B, M = pos.shape
        w = jnp.ones((B,), jnp.float32) if row_weights is None \
            else jnp.asarray(row_weights, jnp.float32)
        kept = jnp.sum(w)
        denoms = (kept * M, kept)
        with jax.default_matmul_precision("highest"):
            params = jax.tree.map(jnp.copy, weights)
            m = jax.tree.map(jnp.zeros_like, params)
            v = jax.tree.map(jnp.zeros_like, params)
            key = jax.random.PRNGKey((int(seed) + 7919) % (2 ** 31 - 1))
            out = {"loss": []}
            for t in range(1, steps + 1):
                acc = jax.tree.map(jnp.zeros_like, params)
                loss = 0.0
                for r0 in range(0, B, self.rows):
                    sl = slice(r0, r0 + self.rows)
                    key, sub = jax.random.split(key)
                    blk = (ids[sl], pos[sl], mlm[sl], nsp[sl], w[sl])
                    l, acc = self._accumulate(params, acc, blk, sub, denoms)
                    loss = loss + l
                out["loss"].append(float(loss))
                if t == 1:
                    out["grad_norm"] = {n: float(x) for n, x in
                                        self._norms(acc).items()}
                params, m, v = self._adam(params, acc, m, v,
                                          jnp.float32(t))
            out["update_norm"] = {n: float(x) for n, x in
                                  self._delta(params, weights).items()}
        return out


# --- the comparison ---------------------------------------------------------

def compare(program, reference):
    """Both arguments: dict(loss, grad_norm, update_norm) as `Reference.run`
    returns. -> ({name: value}, {name: leaf} of the leaf that read worst).
    The numbers LIMITS names are held; the others are information."""
    g_ref, g = reference["grad_norm"], program["grad_norm"]
    u_ref, u = reference["update_norm"], program["update_norm"]
    med = float(np.median(list(g_ref.values())))
    nought = [n for n in g_ref if g_ref[n] < GRAD_NOUGHT * med]
    moved = [n for n in g_ref if n not in nought]
    out, worst = {}, {}
    for i, (a, b) in enumerate(zip(program["loss"], reference["loss"]), 1):
        out["loss%d_rel" % i] = abs(a - b) / abs(b)
    # the worst-leaf gaps, measured against the leaf's or the median leaf's
    # reference norm, whichever is larger
    for key, mine, ref in (("grad_norm_gap", g, g_ref),
                           ("update_norm_gap", u, u_ref)):
        med_k = float(np.median([ref[n] for n in moved]))
        gaps = {n: abs(mine[n] - ref[n]) / max(ref[n], med_k) for n in moved}
        worst[key] = max(gaps, key=gaps.get)
        out[key] = gaps[worst[key]]
    head = [n for n in moved if n.startswith(HEAD)]
    for key, leaves in (("head_grad_gap", head),
                        ("head_bias_grad_gap",
                         [n for n in head if n in STEADY_HEAD])):
        if leaves:
            out[key] = max(abs(g[n] - g_ref[n]) / g_ref[n] for n in leaves)
    enc = {n: 1.0 - g[n] / g_ref[n] for n in moved if n.startswith(ENCODER)}
    if enc:
        worst["encoder_grad_shortfall"] = max(enc, key=enc.get)
        out["encoder_grad_shortfall"] = max(
            0.0, enc[worst["encoder_grad_shortfall"]])
    out["median_update_gap"] = abs(float(np.median(
        [u[n] / u_ref[n] for n in moved])) - 1.0)
    if nought:
        out["nought_grad_share"] = max(g[n] for n in nought) / med
    return out, worst
