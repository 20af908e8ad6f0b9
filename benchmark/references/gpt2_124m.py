"""Plain reference of the GPT-2 decoder as the generation engine serves it.

Straight `jax.numpy`, float32, every matmul at precision "highest", one
full-context causal forward over prompt + served tokens: no paging, no
chunking, no cache, no batching across requests. It imports nothing of
`paddle_tpu` and is handed nothing the program made: the weights come from
`make_weights(cfg, seed)` below (the benchmark hands the SAME arrays to the
engine under the names its `params` dict reads).

What it follows. `openai-community/gpt2` (pre-LN decoder, learned positions,
exact GELU, epsilon 1e-5) as `paddle_tpu/generation/model.py` runs it.
Departures from the published model, all the program's, copied here so that
both sides compute the same function, and listed in the configuration file:
  - no bias on the QKV and attention-output projections;
  - an untied `unembed` matrix (GPT-2 ties it to the token embedding).

What is compared (`gaps`): for each sampled request, at each served
position t, the reference's logits given prompt + served[:t]; the number is
how far the served token's logit lies below the reference's best. Greedy
decoding of a sound engine serves the reference's best token, or at a near
tie one that is lower by rounding only. A CONTROL is the same forward in a
lower precision; it need not decode: at each position the token IT puts first
is read against the float32 logits.

What this holds, and what it does not (chip readings in PERF.md section 6).
The engine keeps float32 arrays and multiplies them at the TPU's default
precision, ONE bf16 pass, so its served tokens lie as far from this
reference's best as a bf16 pass carries them: up to 0.024. A forward with
bfloat16 weights AND activations reads 0.012 to 0.054, no wider: the
comparison holds the engine to bfloat16 matmul operands and CANNOT tell
float32 storage of activations and KV from bfloat16 storage (against a
reference at the default precision it reads the same: engine up to 0.022,
bfloat16 0.014 to 0.041). The controls that fail are the step below bfloat16
operands: `fp8` and `int8` (both operands of every matmul on a per-tensor
grid). Holding float32 storage needs the engine's logits, which the pool
does not return (PERF.md section 7).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# name -> limit, held by every cell of a configuration that names this module.
# Set from chip readings (PERF.md section 6, "PR 26: margins of the
# comparison").
LIMITS = {
    # widest (reference's best logit - reference's logit of the served
    # token) over all sampled served tokens (8 requests, 512 tokens). Logits
    # of these seeded weights are ~N(0, 1). The engine's one-bf16-pass
    # matmuls flip near ties: it reads 0.0017 to 0.0236 over 46 seeds. The
    # float8 control reads 0.234 to 0.52, the int8 control 1.09 to 3.6, a
    # random token about 4. (A bfloat16 control reads 0.012 to 0.054 and
    # passes: see above.)
    "served_logit_gap": 0.08,
}
CONTROLS = ("bfloat16", "fp8", "int8")


def leaf_shapes(cfg):
    h, v = cfg["n_embd"], cfg["vocab_size"]
    m = cfg["mlp_ratio"] * h
    out = {"tok_emb": ((v, h), 0.02), "pos_emb": ((cfg["n_positions"], h),
                                                  0.02),
           "ln_f_g": ((h,), "ones"), "ln_f_b": ((h,), "zeros"),
           "unembed": ((h, v), 1.0 / math.sqrt(h))}
    for i in range(cfg["n_layer"]):
        p = "l%d_" % i
        out.update({
            p + "ln1_g": ((h,), "ones"), p + "ln1_b": ((h,), "zeros"),
            p + "wqkv": ((h, 3 * h), 1.0 / math.sqrt(h)),
            p + "wo": ((h, h), 1.0 / math.sqrt(h)),
            p + "ln2_g": ((h,), "ones"), p + "ln2_b": ((h,), "zeros"),
            p + "w1": ((h, m), 1.0 / math.sqrt(h)),
            p + "b1": ((m,), "zeros"),
            p + "w2": ((m, h), 1.0 / math.sqrt(m)),
            p + "b2": ((h,), "zeros"),
        })
    return out


def make_weights(cfg, seed):
    """All weights on the device in ONE jitted call from the seed, float32
    (the type the engine serves them in). Embeddings N(0, 0.02); matrices
    N(0, 1/sqrt(fan_in)), which keeps activations and logits O(1) through
    12 layers so that a logit gap has a scale."""
    shapes = leaf_shapes(cfg)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, (shape, kind)) in zip(keys, shapes.items()):
            if kind == "ones":
                out[name] = jnp.ones(shape, jnp.float32)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = kind * jax.random.normal(k, shape, jnp.float32)
        return out
    return build(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _quantize(x, kind):
    """x rounded to a lower precision under a per-tensor absmax scale:
    `int8` to the 255 levels of a symmetric grid, `fp8` to float8_e4m3fn."""
    top = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if kind == "int8":
        s = top / 127.0
        return jnp.round(x / s) * s
    s = top / float(jnp.finfo(jnp.float8_e4m3fn).max)
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def forward(params, cfg, tokens, first, count, precision):
    """tokens [T] int32 (padding after the real ones is harmless: the mask
    is causal). -> logits [count, V] float32 at positions first ..
    first+count-1, i.e. the predictions of tokens first+1 .. first+count.
    `precision`: float32 | bfloat16 (weights and activations) | int8 | fp8
    (both operands of every matmul rounded, float32 accumulation)."""
    h, nh = cfg["n_embd"], cfg["n_head"]
    hd = h // nh
    T = tokens.shape[0]
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    p = {k: v.astype(dtype) for k, v in params.items()}

    def mm(a, b):
        if precision in ("int8", "fp8"):
            a, b = _quantize(a, precision), _quantize(b, precision)
        return jnp.matmul(a, b, precision="highest")
    x = p["tok_emb"][tokens] + p["pos_emb"][jnp.arange(T)]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(cfg["n_layer"]):
        q = "l%d_" % i
        xn = _ln(x, p[q + "ln1_g"], p[q + "ln1_b"])
        qkv = mm(xn, p[q + "wqkv"])
        qq, kk, vv = (t.reshape(T, nh, hd).transpose(1, 0, 2)
                      for t in jnp.split(qkv, 3, axis=-1))
        sc = mm(qq, kk.transpose(0, 2, 1)) / math.sqrt(hd)
        sc = jnp.where(causal[None], sc, jnp.asarray(-1e30, sc.dtype))
        o = mm(jax.nn.softmax(sc, axis=-1), vv)
        x = x + mm(o.transpose(1, 0, 2).reshape(T, h), p[q + "wo"])
        xn = _ln(x, p[q + "ln2_g"], p[q + "ln2_b"])
        x = x + mm(_gelu(mm(xn, p[q + "w1"]) + p[q + "b1"]),
                   p[q + "w2"]) + p[q + "b2"]
    x = _ln(x, p["ln_f_g"], p["ln_f_b"])
    xs = jax.lax.dynamic_slice_in_dim(x, first, count, axis=0)
    return mm(xs, p["unembed"]).astype(jnp.float32)


class Reference:
    def __init__(self, cfg, pad_to, new_tokens):
        self.cfg = {k: v for k, v in cfg.items() if isinstance(v, int)}
        self.pad_to, self.new = int(pad_to), int(new_tokens)
        cfg_t = tuple(sorted(self.cfg.items()))

        @functools.partial(jax.jit, static_argnames=("precision",))
        def logits(params, tokens, first, precision):
            return forward(params, dict(cfg_t), tokens, first, self.new,
                           precision)
        self._logits = logits

    def gaps(self, weights, prompt, served, control=None):
        """-> float array [len(served)]: reference's best logit minus the
        reference's logit of the token judged at each served position: the
        served token, or with `control` (a precision) the first choice of the
        forward in that precision."""
        n = len(served)
        if n > self.new or len(prompt) + self.new - 1 > self.pad_to:
            raise ValueError("request longer than the reference was sized "
                             "for: %d + %d" % (len(prompt), n))
        toks = np.zeros((self.pad_to,), np.int32)
        toks[:len(prompt)] = prompt
        toks[len(prompt):len(prompt) + n] = served
        first = jnp.int32(len(prompt) - 1)
        ref = np.asarray(self._logits(weights, jnp.asarray(toks), first,
                                      precision="float32"))[:n]
        judged = np.asarray(served, np.int64)
        if control:
            low = np.asarray(self._logits(
                weights, jnp.asarray(toks), first, precision=control))[:n]
            judged = low.argmax(axis=-1)
        return ref.max(axis=-1) - ref[np.arange(n), judged]


def compare(gaps_per_request):
    """-> {name: value} held against LIMITS."""
    return {"served_logit_gap":
            float(max(float(np.max(g)) for g in gaps_per_request))}
