"""Plain reference of the Ouro looped decoder as the generation engine serves it.

Straight `jax.numpy`, float32, every matmul at precision "highest", one
full-context causal forward over prompt + served tokens: no paging, no
chunking, no cache, no batching across requests. It imports nothing of
`paddle_tpu` and is handed nothing the program made: the weights come from
`make_weights(cfg, seed)` below (the benchmark hands the SAME arrays to the
engine under the names its `params` dict reads).

What it follows: `ByteDance/Ouro-2.6B` `config.json` for every size; for what
the config does not give, the family's published modelling code as recalled
(each item under `assumed` in `configs/ouro_2_6b.json`):

    x = E[token]                                   (no position table)
    for t in 0 .. total_ut_steps - 1:              (a pass)
        for l in 0 .. num_hidden_layers - 1:
            a = Attn_l(RMS1_l(x));  x = x + RMS2_l(a)          (sandwich norms)
            u = RMS3_l(x)
            m = W_down_l(silu(W_gate_l u) * W_up_l u);  x = x + RMS4_l(m)
        x = RMS_f(x)               (the final norm ends EVERY pass)
    logits = W_head x

`Attn`: 16 heads of 128, rotary on the whole head (`rotate_half` form, theta
1e6, the token's position, the same in every pass), causal softmax of
q k^T / sqrt(128), W_o on the merged heads. No bias anywhere; RMSNorm has a
gain and eps 1e-6. The weights of pass t are those of pass 0; its keys and
values are its own (with no cache here, that is simply the recomputation).
The exit gate (`gate_w`, `gate_b`) is among the weights and unused: at the
published `early_exit_threshold` of 1 every token takes every pass.

Stored layout (the engine's, so one set of arrays serves both): the layers'
leaves are stacked `[layers, ...]`; q, k, v share `wqkv [L, h, 3 * 2048]` (q
first, then k, then v), gate and up share `w_gu [L, h, 2 * 5632]` (gate first).

What is compared (`gaps`), as for `gpt2_124m`: for each sampled request, at each
served position t, the reference's logits given prompt + served[:t]; the number
is how far the served token's logit lies below the reference's best. A CONTROL is
the same forward in a lower precision, judged by the token IT puts first.

What this holds, and what it does not. The weights ARE bfloat16 (made so once,
handed to both sides); the reference upcasts them and keeps every activation in
float32. The engine rounds each matmul's activation operand to bfloat16 and each
K and V row to bfloat16 where it is written, accumulates in float32, and keeps
the residual stream, norms, rotary and softmax in float32: through 192 layer
applications that carries the served token up to `served_logit_gap` below the
reference's best. The `bfloat16` control (every activation rounded to bfloat16,
the residual stream too) is information: it is the precision below the engine's
in storage, not in the matmuls. The controls that must fail are `fp8` and `int8`
(both operands of every matmul on a per-tensor grid).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.gpt2_124m import _quantize

# name -> limit, held by every cell of a configuration that names this module.
# Set from chip readings at the cell's own size (benchmark/calibrate.py, one
# process a seed; PERF.md section 6, "PR 29").
LIMITS = {
    # widest (reference's best logit - reference's logit of the served token)
    # over all sampled served tokens (4 requests, 512 tokens). Logits of these
    # seeded weights are ~N(0, 1). Over 20 seeds, 60 samples of 4 requests (my
    # chip runs, PR 29): the engine reads 0.153 to 0.676 (bfloat16 operands
    # and bfloat16 K and V rows through 192 layer applications flip near ties;
    # 0.676 is also the widest of all 300 requests), and 0.255 to 0.798 in the
    # 27 whole runs of the cell; the float8 control 4.39 to 6.65 (no single
    # request under 2.40), the int8 control 3.60 to 6.46 (no single request
    # under 1.81). The limit is the geometric middle of 0.676 and 4.39: twice
    # the engine's widest, two and a half times under float8's narrowest. (The
    # bfloat16 control reads 0.367 to 1.58 and mostly passes: its readings
    # overlap the engine's, see above.)
    "served_logit_gap": 1.7,
}
CONTROLS = ("bfloat16", "fp8", "int8")

WEIGHT_BYTES = 2    # bfloat16 weights
KV_BYTES = 2        # bfloat16 KV pool

KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "intermediate_size", "total_ut_steps", "rope_theta", "rms_norm_eps")
LAYER_LEAVES = ("ln1", "wqkv", "wo", "ln2", "ln3", "w_gu", "w_down", "ln4")


def leaf_shapes(cfg):
    """name -> (shape, N(0, .) scale or "ones" | "zeros")."""
    h, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kd = cfg["num_key_value_heads"] * cfg["head_dim"]
    i = cfg["intermediate_size"]
    return {
        "tok_emb": ((v, h), 0.02),
        "unembed": ((h, v), 1.0 / math.sqrt(h)),
        "norm_f": ((h,), "ones"),
        "gate_w": ((h, 1), 1.0 / math.sqrt(h)), "gate_b": ((1,), "zeros"),
        "ln1": ((n, h), "ones"), "ln2": ((n, h), "ones"),
        "ln3": ((n, h), "ones"), "ln4": ((n, h), "ones"),
        "wqkv": ((n, h, qd + 2 * kd), 1.0 / math.sqrt(h)),
        "wo": ((n, qd, h), 1.0 / math.sqrt(qd)),
        "w_gu": ((n, h, 2 * i), 1.0 / math.sqrt(h)),
        "w_down": ((n, i, h), 1.0 / math.sqrt(i)),
    }


# the weights last made, {(sizes, seed, dtype): arrays}: ONE entry. A run makes
# them twice from one seed (for the engine, then for the comparison), and
# calibrate.py a third time beside a live engine; at 5.34 GB a second copy does
# not fit beside the engine's pools, and the same seed gives the same arrays.
_LAST = {}


def make_weights(cfg, seed, dtype=jnp.bfloat16):
    """All weights on the device in ONE jitted call from the seed: N(0, 0.02)
    embedding, N(0, 1/sqrt(fan_in)) matrices (which keeps every sandwiched
    branch and the logits O(1)), unit gains; drawn in float32 and rounded to
    `dtype` once. A stacked leaf is drawn a layer at a time, so the float32
    draw of the largest (`w_gu`, 4.4 GB whole) is never held whole. Asked again
    for the seed it made last, it hands out the same arrays; asked for another,
    it lets go of those first."""
    shapes = leaf_shapes(cfg)
    key = (tuple(cfg[k] for k in KEYS), int(seed), jnp.dtype(dtype).name)
    if key in _LAST:
        return _LAST[key]
    _LAST.clear()

    @jax.jit
    def build(rng):
        keys = jax.random.split(rng, len(shapes))
        out = {}
        for k, (name, (shape, kind)) in zip(keys, shapes.items()):
            if kind == "ones":
                out[name] = jnp.ones(shape, dtype)
            elif kind == "zeros":
                out[name] = jnp.zeros(shape, dtype)
            elif name in LAYER_LEAVES:
                out[name] = jax.lax.map(
                    lambda kk, s=shape[1:], c=kind: (c * jax.random.normal(
                        kk, s, jnp.float32)).astype(dtype),
                    jax.random.split(k, shape[0]))
            else:
                out[name] = (kind * jax.random.normal(
                    k, shape, jnp.float32)).astype(dtype)
        return out
    _LAST[key] = build(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    return _LAST[key]


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


# planted faults (tests): each takes one assumed item out of the model. (The
# third, passes sharing one cache, has no meaning without a cache: the tests
# plant it in the program.)
FAULTS = ("no_pass_norm", "no_sandwich_norm")


def forward(params, cfg, tokens, first, count, precision, fault=None):
    """tokens [T] int32 (padding after the real ones is harmless: the mask is
    causal). -> logits [count, V] float32 at positions first .. first+count-1.
    `precision`: float32 | bfloat16 (every activation rounded to bfloat16) |
    int8 | fp8 (both operands of every matmul rounded, float32 accumulation).
    Each layer's weights are upcast inside the loop."""
    nh, hd = cfg["num_attention_heads"], cfg["head_dim"]
    kvh, inter = cfg["num_key_value_heads"], cfg["intermediate_size"]
    eps, T = cfg["rms_norm_eps"], tokens.shape[0]
    act = jnp.bfloat16 if precision == "bfloat16" else jnp.float32

    def mm(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if precision in ("int8", "fp8"):
            a, b = _quantize(a, precision), _quantize(b, precision)
        return jnp.matmul(a, b, precision="highest").astype(act)

    def norm(x, g):
        return _rms(x.astype(jnp.float32), g.astype(jnp.float32),
                    eps).astype(act)
    pos = jnp.arange(T, dtype=jnp.float32)
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                       / hd))
    ang = jnp.concatenate([pos[:, None] * inv[None, :]] * 2, axis=-1)
    cos, sin = jnp.cos(ang)[None], jnp.sin(ang)[None]      # [1, T, hd]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def heads(t, n):
        return t.reshape(T, n, hd).transpose(1, 0, 2).astype(jnp.float32)

    def layer(x, w):
        qkv = mm(norm(x, w["ln1"]), w["wqkv"])
        q = heads(qkv[:, :nh * hd], nh)
        k = heads(qkv[:, nh * hd:(nh + kvh) * hd], kvh)
        v = heads(qkv[:, (nh + kvh) * hd:], kvh)
        q = (q * cos + _rotate_half(q) * sin).astype(act)
        k = (k * cos + _rotate_half(k) * sin).astype(act)
        if kvh != nh:
            k, v = (jnp.repeat(t, nh // kvh, axis=0) for t in (k, v))
        sc = mm(q, k.transpose(0, 2, 1)).astype(jnp.float32) / math.sqrt(hd)
        sc = jnp.where(causal[None], sc, -1e30)
        o = mm(jax.nn.softmax(sc, axis=-1).astype(act), v.astype(act))
        a = mm(o.transpose(1, 0, 2).reshape(T, nh * hd), w["wo"])
        x = x + (a if fault == "no_sandwich_norm" else norm(a, w["ln2"]))
        gu = mm(norm(x, w["ln3"]), w["w_gu"]).astype(jnp.float32)
        m = mm((jax.nn.silu(gu[:, :inter]) * gu[:, inter:]).astype(act),
               w["w_down"])
        return x + norm(m, w["ln4"]), None
    stack = {n: params[n] for n in LAYER_LEAVES}
    x = params["tok_emb"][tokens].astype(act)
    for t in range(cfg["total_ut_steps"]):
        x, _ = jax.lax.scan(layer, x, stack)
        if fault != "no_pass_norm" or t == cfg["total_ut_steps"] - 1:
            x = norm(x, params["norm_f"])
    xs = jax.lax.dynamic_slice_in_dim(x, first, count, axis=0)
    return mm(xs, params["unembed"]).astype(jnp.float32)


class Reference:
    def __init__(self, cfg, pad_to, new_tokens):
        self.cfg = {k: cfg[k] for k in KEYS}
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


# --- what the algorithm NEEDS, from shapes (read by metrics/config_mfu_pct.py
# and metrics/step_hbm_roofline_pct.py) ----------------------------------------

def _layer_matrix_params(cfg):
    h = cfg["hidden_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kd = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * (qd + 2 * kd) + qd * h + 3 * h * cfg["intermediate_size"]


def request_flops(cfg, prompt_len, new_tokens):
    """Forward FLOPs the model needs to serve one request: every prompt and
    generated position but the last through the stack `total_ut_steps` times (2
    per matrix parameter of a layer; 4 * heads * head_dim per attended position
    per layer application: the score and the context products), and the head for
    the `new_tokens` sampled positions only. Norms, rotary and the exit gate are
    left out (under a thousandth)."""
    n = prompt_len + new_tokens - 1          # positions run through
    apps = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    attended = n * (n + 1) // 2              # sum of context lengths
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    return (2 * _layer_matrix_params(cfg) * apps * n + 4 * qd * apps * attended
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * new_tokens)


def step_bytes(cfg, steps, attended_tokens):
    """Bytes `steps` mixed steps NEED from HBM: the stack's matrices once a pass
    a step, the head once a step, and the K and V rows of the positions the live
    slots attended (`attended_tokens`, the program's counter) in each of the
    passes x layers of cache. Nothing a kernel re-reads, no activations, no
    write of the new rows (24 rows a step), no embedding rows."""
    apps = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    weights = (_layer_matrix_params(cfg) * apps
               + cfg["hidden_size"] * cfg["vocab_size"]) * WEIGHT_BYTES
    kv_row = cfg["num_key_value_heads"] * cfg["head_dim"]
    return steps * weights + attended_tokens * apps * 2 * kv_row * KV_BYTES
