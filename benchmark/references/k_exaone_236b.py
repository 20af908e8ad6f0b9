"""Plain reference of K-EXAONE-236B-A23B's language model, one chip's share of it,
as the generation engine serves it.

Straight `jax.numpy`, float32, every matmul at precision "highest", one
full-context causal forward over prompt + served tokens: no paging, no chunking,
no cache, no batching across requests, no sorting of tokens by expert (a loop
over the experts held, each on every token under a mask). It imports nothing of
`paddle_tpu` and is handed nothing the program made: the weights come from
`make_weights(cfg, seed)` below (the benchmark hands the SAME arrays to the
engine under the names its `params` dict reads).

What it follows: `LGAI-EXAONE/K-EXAONE-236B-A23B` `config.json` (`model_type`
`exaone_moe`) for every size; for what the config does not say, EXAONE 4.0's
published modelling code as recalled, the family `exaone_moe` builds on (each
item under `assumed` in `configs/k_exaone_236b.json`). `x` has `hidden_size`
entries; layer `l`:

    a = Attn_l(x);   x = x + RMS_a,l(a)          (the norm on the branch's OUTPUT)
    m = FFN_l(x);    x = x + RMS_f,l(m)
    Attn_l: q = W_q x (64 heads of 128), k = W_k x, v = W_v x (8 heads of 128);
            q, k each normed per head (a gain of 128); rotary (rotate_half, the
            whole head, theta 1e6) on q and k in a `sliding_attention` layer,
            NONE in a `full_attention` layer; query head h reads key-value head
            h // 8; scores q.k / sqrt(128); key j is visible to query i iff
            j <= i, and in a sliding layer also i - j < 128; softmax in float32;
            W_o on the merged heads
    FFN_0 (`mlp_layer_types[0]` dense): W_down(silu(W_gate x) * W_up x)
    FFN_l (sparse): s = sigmoid(W_r x), 128 scores; T = the 8 largest of s + b
            (b: a bias used for the CHOICE only; `n_group` 1, `topk_group` 1: no
            grouping); w_e = 2.5 * s_e / sum_{j in T} s_j  (`norm_topk_prob`,
            `routed_scaling_factor`);
            m = sum_{e in T, e HELD} w_e E_e(x) + S(x)
    logits = W_head RMS_f(x)                     (untied; over the slice held)

THE CHIP'S SHARE. The file gives the experts held (`num_experts`, the first of
them under `experts_held.first`) beside the router's width
(`num_experts_published`). The router scores all 128, the choice is among all
128 and the weights are normalised over all 8 chosen; the sum runs over the held
experts alone, here as in the program. The vocabulary's slice is a smaller
vocabulary. `whole_layer()` below is the same layer with every expert held: the
tests add the eight shares up to it.

Stored layout (the engine's, so one set of arrays serves both): the leading
dense layers' leaves are stacked `[dense layers, ...]` under `d_<name>`, the
sparse layers' `[sparse layers, ...]`; q, k, v share `wqkv` (q first, then k,
then v), gate and up share `w_gu` / `e_gu` / `s_gu` (gate first); the experts'
are `e_gu [L, E held, h, 2 * 2048]`, `e_down [L, E held, 2048, h]`.

What is compared (`gaps`), as for `ouro_2_6b`: for each sampled request, at each
served position t, the reference's logits given prompt + served[:t]; the number
is how far the served token's logit lies below the reference's best. A CONTROL
is the same forward in a lower precision, judged by the token IT puts first.

The weights ARE bfloat16 (made so once, handed to both sides); the reference
upcasts them a leaf at a time, an expert at a time, where it multiplies, and
keeps every activation in float32. The engine rounds each matmul's activation
operand to bfloat16 and each K and V row to bfloat16 where it is written,
accumulates in float32, and keeps the residual stream, norms, rotary, softmax
and the router's scores in float32. The controls that must fail are `fp8` and
`int8` (both operands of every matmul on a per-tensor grid, the router's too);
`bfloat16` (every activation rounded, the residual stream too) is information.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.gpt2_124m import _quantize
from benchmark.references.ouro_2_6b import _rms, _rotate_half

# name -> limit, held by every cell of a configuration that names this module.
# Read on the chip at the cell's own size (k_exaone_236b_reason_c128, my chip
# runs, PR 38): benchmark/calibrate.py for one seed (129 requests; it keeps each
# request's widest gap only) and its serve loop with every gap kept for two more
# (128 and 130 requests), each request judged against the reference and against
# the three controls; "a sample" is four requests in the order they ended, what
# a run's `check_requests` reads: 96 samples of the widest, 64 of the mean.
LIMITS = {
    # MEAN over all sampled served tokens (4 requests, 2,048 tokens) of
    # (reference's best logit - reference's logit of the served token): 0 where
    # the served token is the reference's first choice. This is the number that
    # tells the precisions apart: the engine reads 0.00049 to 0.0020 a sample
    # (bfloat16 operands and K and V rows flip 1.7-3.0 % of the tokens, at near
    # ties), the float8 control 0.0569 to 0.0730 (31-36 % of its first choices
    # are not the reference's), the int8 control 0.130 to 0.224 (46-60 %). The
    # limit is the geometric middle of 0.0020 and 0.0569: five times the
    # engine's widest, five and a half times under float8's narrowest. (The
    # bfloat16 control reads 0.0012 to 0.0034 and PASSES: it is the precision
    # below the engine's in storage, not in the matmuls.)
    "served_logit_gap_mean": 0.01,
    # WIDEST such gap over the sample. Five layers carry too little rounding
    # for this one to tell float8 from bfloat16: the engine reads 0.196 to 0.776
    # a sample (0.26 and 0.54 in two whole runs), the float8 control 0.718 to
    # 1.33 and the int8 control 1.24 to 2.01, so BOTH CONTROLS PASS it in part
    # (the mean above is what fails them). It is held for what the mean cannot
    # see: ONE wrong token among 2,048 moves the mean by 0.002 and reads here
    # the distance from the reference's best logit to a logit drawn at random
    # (logits of these seeded weights are ~N(0, 1) over 19,200 rows: 3.9 less
    # N(0, 1), over 1.55 in 99 of 100). The limit is twice the engine's widest.
    "served_logit_gap": 1.55,
}
CONTROLS = ("bfloat16", "fp8", "int8")

WEIGHT_BYTES = 2    # bfloat16 weights
KV_BYTES = 2        # bfloat16 KV pool

ATTN_LEAVES = ("wqkv", "q_norm", "k_norm", "wo", "ln_attn", "ln_ffn")
DENSE_LEAVES = ATTN_LEAVES + ("w_gu", "w_down")
SPARSE_LEAVES = ATTN_LEAVES + ("router", "router_bias", "e_gu", "e_down",
                               "s_gu", "s_down")
EXPERT_LEAVES = ("e_gu", "e_down")      # two leading axes: layer, expert


def sizes(cfg):
    """What the forward needs of the configuration's file, as plain numbers and
    tuples (hashable: the jitted forward is keyed by it)."""
    n = cfg["num_hidden_layers"]
    kinds = list(cfg["mlp_layer_types"])[:n]
    dense = next((i for i, k in enumerate(kinds) if k != "dense"), n)
    if any(k != "sparse" for k in kinds[dense:]):
        raise ValueError("a dense layer after a sparse one: %r" % kinds)
    windows = tuple(int(cfg["sliding_window"]) if t == "sliding_attention"
                    else 0 for t in list(cfg["layer_types"])[:n])
    rope = cfg.get("rope_parameters") or cfg
    held = cfg["num_experts"]
    return dict(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"], layers=n,
        dense_layers=dense, heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        experts=cfg.get("num_experts_published", held), held=held,
        first=(cfg.get("experts_held") or {}).get("first", 0),
        per_tok=cfg["num_experts_per_tok"],
        shared=cfg["num_shared_experts"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]), eps=cfg["rms_norm_eps"],
        theta=float(rope["rope_theta"]), windows=windows)


def leaf_shapes(cfg):
    """name -> (shape, N(0, .) scale or "ones")."""
    z = sizes(cfg)
    h, v, d = z["hidden"], z["vocab"], z["head_dim"]
    qd, kd = z["heads"] * d, z["kv_heads"] * d
    m, ms = z["expert_width"], z["shared"] * z["expert_width"]

    def attn(n):
        return {"wqkv": ((n, h, qd + 2 * kd), 1.0 / math.sqrt(h)),
                "q_norm": ((n, d), "ones"), "k_norm": ((n, d), "ones"),
                "wo": ((n, qd, h), 1.0 / math.sqrt(qd)),
                "ln_attn": ((n, h), "ones"), "ln_ffn": ((n, h), "ones")}
    out = {"tok_emb": ((v, h), 0.02), "unembed": ((h, v), 1.0 / math.sqrt(h)),
           "norm_f": ((h,), "ones")}
    nd, ns = z["dense_layers"], z["layers"] - z["dense_layers"]
    if nd:
        i = z["dense_width"]
        dense = dict(attn(nd), w_gu=((nd, h, 2 * i), 1.0 / math.sqrt(h)),
                     w_down=((nd, i, h), 1.0 / math.sqrt(i)))
        out.update({"d_" + k: s for k, s in dense.items()})
    if ns:
        e = z["held"]
        out.update(attn(ns))
        out.update({
            "router": ((ns, h, z["experts"]), 1.0 / math.sqrt(h)),
            # the choice bias: small beside the scores' spread and NOT zero,
            # so that leaving it out changes which experts run
            "router_bias": ((ns, z["experts"]), 0.01),
            "e_gu": ((ns, e, h, 2 * m), 1.0 / math.sqrt(h)),
            "e_down": ((ns, e, m, h), 1.0 / math.sqrt(m)),
            "s_gu": ((ns, h, 2 * ms), 1.0 / math.sqrt(h)),
            "s_down": ((ns, ms, h), 1.0 / math.sqrt(ms))})
    return out


# the weights last made, {(sizes, seed, dtype): arrays}: ONE entry. A run makes
# them twice from one seed (for the engine, then for the comparison), and
# calibrate.py a third time beside a live engine; at 7.4 GB a second copy does
# not fit, and the same seed gives the same arrays.
_LAST = {}


def make_weights(cfg, seed, dtype=jnp.bfloat16):
    """All weights on the device in ONE jitted call from the seed: N(0, 0.02)
    embedding, N(0, 1/sqrt(fan_in)) matrices (which keeps every normed branch
    and the logits O(1)), unit gains, the choice bias N(0, 0.01); drawn in
    float32 and rounded to `dtype` once. A stacked leaf is drawn a layer at a
    time, the experts' an expert at a time, so the float32 draw of the largest
    (`e_gu`, 9.7 GB whole) is never held whole. Asked again for the seed it made
    last, it hands out the same arrays; asked for another, it lets go of those
    first."""
    shapes = leaf_shapes(cfg)
    key = (tuple(sorted(sizes(cfg).items())), int(seed), jnp.dtype(dtype).name)
    if key in _LAST:
        return _LAST[key]
    _LAST.clear()

    @jax.jit
    def build(rng):
        keys = jax.random.split(rng, len(shapes))
        out = {}
        for k, (name, (shape, kind)) in zip(keys, shapes.items()):
            lead = 2 if name in EXPERT_LEAVES else \
                0 if name in ("tok_emb", "unembed", "norm_f") else 1
            if kind == "ones":
                out[name] = jnp.ones(shape, dtype)
            elif lead:
                n = int(np.prod(shape[:lead]))
                out[name] = jax.lax.map(
                    lambda kk, s=shape[lead:], c=kind: (c * jax.random.normal(
                        kk, s, jnp.float32)).astype(dtype),
                    jax.random.split(k, n)).reshape(shape)
            else:
                out[name] = (kind * jax.random.normal(
                    k, shape, jnp.float32)).astype(dtype)
        return out
    _LAST[key] = build(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    return _LAST[key]


# planted faults (tests): each takes one item of the equations out, or bends it
FAULTS = ("no_choice_bias", "norm_over_held", "no_routed_scaling",
          "window_off_by_one", "rotary_on_full", "kv_head_mod")


def _mm_of(precision):
    act = jnp.bfloat16 if precision == "bfloat16" else jnp.float32

    def mm(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if precision in ("int8", "fp8"):
            a, b = _quantize(a, precision), _quantize(b, precision)
        return jnp.matmul(a, b, precision="highest").astype(act)
    return mm, act


def routing(z, x, router, bias, mm, fault=None):
    """x [T, h] -> (chosen [T, k] among ALL experts, their weights [T, k]
    normalised over all k chosen, the scores [T, experts])."""
    s = jax.nn.sigmoid(mm(x, router).astype(jnp.float32))
    b = 0.0 if fault == "no_choice_bias" else bias.astype(jnp.float32)
    _, chosen = jax.lax.top_k(s + b, z["per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if z["norm_topk"]:
        if fault == "norm_over_held":
            here = (chosen >= z["first"]) & (chosen < z["first"] + z["held"])
            w = w / jnp.maximum(jnp.sum(jnp.where(here, w, 0.0), axis=-1,
                                        keepdims=True), 1e-30)
        else:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
    if fault != "no_routed_scaling":
        w = w * z["scaling"]
    return chosen, w


def sparse_ffn(z, x, w, mm, act, fault=None, shared=True):
    """The sparse feed-forward on x [T, h]: the held experts' part (each expert
    on every token, weighted by what the router gave that token for it, 0 where
    it was not chosen) and, with `shared`, the shared expert."""
    m = z["expert_width"]
    chosen, wts = routing(z, x, w["router"], w["router_bias"], mm, fault)

    def gated(x, gu_w, down_w):
        gu = mm(x, gu_w).astype(jnp.float32)
        i = gu.shape[-1] // 2
        return mm((jax.nn.silu(gu[:, :i]) * gu[:, i:]).astype(act), down_w)

    def one(total, xs):
        e, gu_w, down_w = xs
        weight = jnp.sum(jnp.where(chosen == z["first"] + e, wts, 0.0),
                         axis=-1, keepdims=True)            # [T, 1]
        y = gated(x, gu_w, down_w).astype(jnp.float32)
        return total + weight * y, None
    total, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (jnp.arange(z["held"]), w["e_gu"], w["e_down"]))
    if shared:
        total = total + gated(x, w["s_gu"], w["s_down"]).astype(jnp.float32)
    return total.astype(act)


def forward(params, z, tokens, first, count, precision, fault=None):
    """tokens [T] int32 (padding after the real ones is harmless: the mask is
    causal). -> logits [count, V] float32 at positions first .. first+count-1.
    `z`: `sizes(cfg)`. `precision`: float32 | bfloat16 (every activation
    rounded to bfloat16) | int8 | fp8 (both operands of every matmul rounded,
    float32 accumulation). Each layer's weights are upcast inside the loop, the
    experts' an expert at a time."""
    nh, kvh, hd = z["heads"], z["kv_heads"], z["head_dim"]
    eps, T = z["eps"], tokens.shape[0]
    mm, act = _mm_of(precision)

    def norm(x, g):
        return _rms(x.astype(jnp.float32), g.astype(jnp.float32),
                    eps).astype(act)
    pos = jnp.arange(T, dtype=jnp.float32)
    inv = 1.0 / (z["theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.concatenate([pos[:, None] * inv[None, :]] * 2, axis=-1)
    cos, sin = jnp.cos(ang)[None], jnp.sin(ang)[None]      # [1, T, hd]
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]

    def heads(t, n):
        return t.reshape(T, n, hd).transpose(1, 0, 2).astype(jnp.float32)

    def layer(x, w, window):
        qkv = mm(x, w["wqkv"])
        q = norm(heads(qkv[:, :nh * hd], nh), w["q_norm"])
        k = norm(heads(qkv[:, nh * hd:(nh + kvh) * hd], kvh), w["k_norm"])
        v = heads(qkv[:, (nh + kvh) * hd:], kvh)
        if window or fault == "rotary_on_full":
            q = (q * cos + _rotate_half(q.astype(jnp.float32)) * sin
                 ).astype(act)
            k = (k * cos + _rotate_half(k.astype(jnp.float32)) * sin
                 ).astype(act)
        rep = nh // kvh
        if fault == "kv_head_mod":      # head h over key-value head h % 8
            k, v = (jnp.tile(t, (rep, 1, 1)) for t in (k, v))
        else:                           # head h over key-value head h // 8
            k, v = (jnp.repeat(t, rep, axis=0) for t in (k, v))
        sc = mm(q, k.transpose(0, 2, 1)).astype(jnp.float32) / math.sqrt(hd)
        visible = j <= i
        if window:
            reach = window + 1 if fault == "window_off_by_one" else window
            visible = visible & (i - j < reach)
        sc = jnp.where(visible[None], sc, -1e30)
        o = mm(jax.nn.softmax(sc, axis=-1).astype(act), v.astype(act))
        a = mm(o.transpose(1, 0, 2).reshape(T, nh * hd), w["wo"])
        x = x + norm(a, w["ln_attn"])
        if "w_gu" in w:
            gu = mm(x, w["w_gu"]).astype(jnp.float32)
            half = gu.shape[-1] // 2
            m = mm((jax.nn.silu(gu[:, :half]) * gu[:, half:]).astype(act),
                   w["w_down"])
        else:
            m = sparse_ffn(z, x, w, mm, act, fault)
        return x + norm(m, w["ln_ffn"])
    x = params["tok_emb"][tokens].astype(act)
    nd = z["dense_layers"]
    for l in range(z["layers"]):
        names, prefix, at = (DENSE_LEAVES, "d_", l) if l < nd else \
            (SPARSE_LEAVES, "", l - nd)
        x = layer(x, {n: params[prefix + n][at] for n in names},
                  z["windows"][l])
    x = norm(x, params["norm_f"])
    xs = jax.lax.dynamic_slice_in_dim(x, first, count, axis=0)
    return mm(xs, params["unembed"]).astype(jnp.float32)


def whole_layer(cfg, w, x, first=0, held=None, shared=True):
    """One sparse feed-forward (tests): x [T, h] float32 through the router and
    the experts `first .. first + held - 1` of `w` (a layer's leaves, the
    experts' leaves holding exactly those), with or without the shared
    expert."""
    z = dict(sizes(cfg), first=first)
    if held is not None:
        z["held"] = held
    mm, act = _mm_of("float32")
    with jax.default_matmul_precision("highest"):
        return sparse_ffn(z, x, w, mm, act, shared=shared)


class Reference:
    def __init__(self, cfg, pad_to, new_tokens, fault=None):
        z = sizes(cfg)
        self.pad_to, self.new = int(pad_to), int(new_tokens)
        z_t = tuple(sorted(z.items()))

        @functools.partial(jax.jit, static_argnames=("precision",))
        def logits(params, tokens, first, precision):
            with jax.default_matmul_precision("highest"):
                return forward(params, dict(z_t), tokens, first, self.new,
                               precision, fault)
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
    """-> {name: value} held against LIMITS: the mean gap over every sampled
    served token, and the widest."""
    every = np.concatenate([np.asarray(g, np.float64)
                            for g in gaps_per_request])
    return {"served_logit_gap_mean": float(every.mean()),
            "served_logit_gap": float(every.max())}


# --- what the algorithm NEEDS, from shapes (read by metrics/config_mfu_pct.py
# and metrics/moe_experts_hbm_roofline_pct.py) ---------------------------------

def _attn_params(z):
    qd, kd = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    return z["hidden"] * (qd + 2 * kd) + qd * z["hidden"]


def _expert_params(z):
    return 3 * z["hidden"] * z["expert_width"]


def request_flops(cfg, prompt_len, new_tokens):
    """Forward FLOPs this chip's share NEEDS to serve one request: every prompt
    and generated position but the last through the stack (2 per matrix
    parameter it meets; 4 * heads * head_dim per attended position a layer: the
    score and the context products), and the head for the `new_tokens` sampled
    positions only.

    The routed experts: a token chooses `num_experts_per_tok` of
    `num_experts_published`, of which `num_experts` are held here, so the pairs
    EXPECTED here are per_tok * held / published a token a layer (one, at 8 of
    128 with 16 held). That expectation is what is counted, not the pairs a
    run's routing happened to send here: the count is of the model's need, the
    same for every seed. The shared expert and the router run for every token.
    A window layer attends at most its window. Norms, rotary, the sigmoid and
    the sort are left out (under a thousandth)."""
    z = sizes(cfg)
    n = prompt_len + new_tokens - 1          # positions run through
    qd = z["heads"] * z["head_dim"]
    dense, sparse = z["dense_layers"], z["layers"] - z["dense_layers"]
    pairs = z["per_tok"] * z["held"] / z["experts"]
    per_token = (z["layers"] * _attn_params(z)
                 + dense * 3 * z["hidden"] * z["dense_width"]
                 + sparse * (z["hidden"] * z["experts"]
                             + (z["shared"] + pairs) * _expert_params(z)))
    attended = 0
    for w in z["windows"]:
        # position i attends i + 1 keys, a window layer at most `w`
        full = n * (n + 1) // 2
        if w and n > w:
            full -= (n - w) * (n - w + 1) // 2
        attended += full
    return (2 * per_token * n + 4 * qd * attended
            + 2 * z["hidden"] * z["vocab"] * new_tokens)


def expert_bytes(cfg, experts_touched):
    """Bytes the grouped products NEED from HBM for `experts_touched`
    layer-experts that had at least one token (the program's counter): each
    one's three matrices once. An expert no token chose costs nothing; the
    activations (a few rows) are left out."""
    return experts_touched * _expert_params(sizes(cfg)) * WEIGHT_BYTES
