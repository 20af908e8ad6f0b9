"""Plain reference of Kimi-K2.6's language model, one chip's share of it, as the
generation engine serves it.

Straight `jax.numpy`, float32, every matmul at precision "highest", one
full-context causal forward over prompt + served tokens in the PUBLISHED form of
multi-head latent attention: `W_kvb` decompresses every head's keys and values
from the latent, and the scores are q . k over whole heads. No absorbed
projection, no latent cache, no paging, no chunking, no batching across requests,
no sorting of tokens by expert (a loop over the experts held, each on every token
under a mask). It imports nothing of `paddle_tpu` and is handed nothing the
program made: the weights come from `make_weights(cfg, seed)` below (the
benchmark hands the SAME arrays to the engine under the names its `params` dict
reads).

What it follows: `moonshotai/Kimi-K2.6` `config.json` (`model_type` `kimi_k2`)
for every size; for what the config does not say, DeepSeek-V3's published
modelling code as recalled, which `kimi_k2` runs (each item under `assumed` in
`configs/kimi_k2_6.json`). `x` has `hidden_size` entries; layer `l`:

    h = x + Attn(RMS_in(x));   x = h + FFN(RMS_post(h))          (pre-norm, eps 1e-5)
    Attn: c_q = RMS_qa(W_qa x) [1536];  q = W_qb c_q -> 64 heads x (q_nope 128 | q_rope 64)
          W_kva x -> (c 512 | k_rope 64);  c = RMS_kva(c)
          (k_nope_h | v_h) = W_kvb,h c                            (128 | 128 a head)
          q_rope, k_rope: YaRN rotary of the position, k_rope ONE for all heads
          s_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) * 192^-1/2 * m^2, causal
          out = W_o [softmax(s_h) v_h over the heads]
    m = 0.1 * mscale_all_dim * ln(factor) + 1 (YaRN's attention scale)
    FFN_0 (first_k_dense_replace 1): W_down(silu(W_gate x) * W_up x), width 18432
    FFN_l: s = sigmoid(W_r x), 384 scores; T = the 8 largest of s + b (b: the
           correction bias, for the CHOICE only; n_group 1: no grouping);
           w_e = 2.827 * s_e / sum_{j in T} s_j;  m = sum_{e in T, e HELD} w_e E_e(x) + S(x)
    logits = W_head RMS_f(x)                            (untied; over the slice held)

YaRN (rope_scaling): for the 32 pairs of the 64-wide rotary part, theta 50,000,
the frequency of pair i is theta^(-2i/64) where the pair turns fast (above
beta_fast rotations over the original 4,096 positions), that over `factor` where
it turns slowly (under beta_slow), and a linear blend between; the pairs are
the INTERLEAVED lanes (2i, 2i + 1), each turned in place by its angle.

THE CHIP'S SHARE. The file gives the experts held (`n_routed_experts`, the first
of them under `experts_held.first`) beside the router's width
(`n_routed_experts_published`). The router scores all 384, the choice is among
all 384 and the weights are normalised over all 8 chosen; the sum runs over the
held experts alone, here as in the program. The vocabulary's slice is a smaller
vocabulary. `whole_layer()` below is one sparse feed-forward with any experts
held: the tests add the shares up to the uncut layer.

Stored layout (the engine's, so one set of arrays serves both): the leading
dense layer's leaves are stacked `[dense layers, ...]` under `d_<name>`, the
sparse layers' `[sparse layers, ...]`; `wq_b`'s columns are head by head (nope
128, then rope 64), `wkv_a`'s the latent then the rotary key, `wkv_b`'s head by
head (k_nope 128, then v 128); gate and up share `w_gu` / `e_gu` / `s_gu` (gate
first); the experts' are `e_gu [L, E held, h, 2 * 2048]`, `e_down [L, E held,
2048, h]`.

BLOCKED. A request's context reaches 10,240 positions, and the reference runs
beside what a run leaves on the chip: the attention goes 8 heads at a time
(their keys and values over all positions decompressed, `[T, 8, 256]`) and,
within them, 256 query positions at a time (scores `[8, 256, T]`); the dense
feed-forward and W_o go 256 positions at a time; an expert's matrices are read
where they lie. Nothing of the size `[T, T]`, `[T, 64, 256]` or `[T, 18432]` is
made.

What is compared (`gaps`), as for `k_exaone_236b`: for each sampled request, at
each served position t, the reference's logits given prompt + served[:t]; the
number is how far the served token's logit lies below the reference's best. A
CONTROL is this forward bent one way, judged by the token IT puts first:
`fp8_latent` stores the latent rows (c and k_rope) in float8 e4m3 under a
per-row absmax scale, the precision below the configuration's bfloat16 pool;
`no_mscale` drops YaRN's m^2 from the softmax scale.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.ouro_2_6b import _rms

# name -> limit, held by every cell of a configuration that names this module.
# Readings on a v5e, 4 requests (8,192 served tokens) a sample (PERF.md).
LIMITS = {
    # MEAN over all sampled served tokens of (reference's best logit -
    # reference's logit of the served token): 0 where the served token is the
    # reference's first choice. The engine reads 0.0037 to 0.0052 a run
    # (bfloat16 rows, queries and probabilities flip 5.9-6.3 % of the tokens,
    # at near ties). The limit is about twice the engine's widest.
    "served_logit_gap_mean": 0.01,
    # WIDEST such gap over the sample: the engine reads 0.96 to 1.40 a run
    # (11 runs), and its tail over 8,192 tokens thins about fourfold each
    # 0.3 (a run holds 16 gaps over 0.5, 1.25 over 0.9, 0.5 over 1.3), so
    # 1.55 would be crossed in about one run of five. The limit is about
    # twice the engine's widest: held for ONE wrong token, which the mean
    # cannot see.
    "served_logit_gap": 3.0,
}
CONTROLS = ("fp8_latent", "no_mscale")

WEIGHT_BYTES = 2    # bfloat16 weights
LATENT_BYTES = 2    # bfloat16 latent pool

ATTN_LEAVES = ("ln_attn", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
               "wkv_b", "wo", "ln_ffn")
DENSE_LEAVES = ATTN_LEAVES + ("w_gu", "w_down")
SPARSE_LEAVES = ATTN_LEAVES + ("router", "router_bias", "e_gu", "e_down",
                               "s_gu", "s_down")
EXPERT_LEAVES = ("e_gu", "e_down")      # two leading axes: layer, expert
BLOCK = 256                             # query positions a block
HEADS = 8                               # heads a group


def sizes(cfg):
    """What the forward needs of the configuration's file, as plain numbers
    (hashable: the jitted forward is keyed by it)."""
    held = cfg["n_routed_experts"]
    rope = cfg["rope_scaling"]
    return dict(
        vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        experts=cfg.get("n_routed_experts_published", held), held=held,
        first=(cfg.get("experts_held") or {}).get("first", 0),
        per_tok=cfg["num_experts_per_tok"], shared=cfg["n_shared_experts"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]), eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]), factor=float(rope["factor"]),
        original=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        mscale=float(rope["mscale"]),
        mscale_all_dim=float(rope["mscale_all_dim"]))


def leaf_shapes(cfg):
    """name -> (shape, N(0, .) scale or "ones")."""
    z = sizes(cfg)
    h, v, nh = z["hidden"], z["vocab"], z["heads"]
    qr, kr = z["q_rank"], z["kv_rank"]
    m, ms = z["expert_width"], z["shared"] * z["expert_width"]

    def attn(n):
        return {"ln_attn": ((n, h), "ones"),
                "wq_a": ((n, h, qr), 1.0 / math.sqrt(h)),
                "q_a_norm": ((n, qr), "ones"),
                "wq_b": ((n, qr, nh * (z["nope"] + z["rope"])),
                         1.0 / math.sqrt(qr)),
                "wkv_a": ((n, h, kr + z["rope"]), 1.0 / math.sqrt(h)),
                "kv_a_norm": ((n, kr), "ones"),
                "wkv_b": ((n, kr, nh * (z["nope"] + z["v"])),
                          1.0 / math.sqrt(kr)),
                "wo": ((n, nh * z["v"], h), 1.0 / math.sqrt(nh * z["v"])),
                "ln_ffn": ((n, h), "ones")}
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
            # the correction bias: small beside the scores' spread and NOT
            # zero, so that leaving it out changes which experts run
            "router_bias": ((ns, z["experts"]), 0.01),
            "e_gu": ((ns, e, h, 2 * m), 1.0 / math.sqrt(h)),
            "e_down": ((ns, e, m, h), 1.0 / math.sqrt(m)),
            "s_gu": ((ns, h, 2 * ms), 1.0 / math.sqrt(h)),
            "s_down": ((ns, ms, h), 1.0 / math.sqrt(ms))})
    return out


# the weights last made, {(sizes, seed, dtype): arrays}: ONE entry. A run makes
# them twice from one seed (for the engine, then for the comparison), and
# calibrate.py a third time beside a live engine; at 7 GB a second copy does not
# fit, and the same seed gives the same arrays.
_LAST = {}


def make_weights(cfg, seed, dtype=jnp.bfloat16):
    """All weights on the device in ONE jitted call from the seed: N(0, 0.02)
    embedding, N(0, 1/sqrt(fan_in)) matrices (which keeps every normed branch
    and the logits O(1)), unit gains, the correction bias N(0, 0.01); drawn in
    float32 and rounded to `dtype` once. A stacked leaf is drawn a layer at a
    time, the experts' an expert at a time. Asked again for the seed it made
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
FAULTS = ("rope_halves", "no_yarn", "no_mscale", "no_kv_norm",
          "no_choice_bias", "no_routed_scaling")


def _mm(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision="highest")


def yarn_frequencies(z, fault=None):
    """[rope / 2] float64: the angle a position turns pair i by."""
    d, base = z["rope"], z["theta"]
    fast = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if fault == "no_yarn":
        return fast
    slow = fast / z["factor"]

    def pair_at(rotations):
        # the pair that turns `rotations` times over the original context
        return d * math.log(z["original"] / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    lo = max(math.floor(pair_at(z["beta_fast"])), 0)
    hi = min(math.ceil(pair_at(z["beta_slow"])), d - 1)
    if hi == lo:
        hi += 0.001
    # 0 up to pair lo (fast pairs: extrapolated), 1 from pair hi (slow ones:
    # interpolated), linear between
    blend = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    return fast * (1.0 - blend) + slow * blend


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _turn(x, cos, sin, fault=None):
    """x [..., d] of interleaved pairs (x_2i, x_2i+1), each turned by its
    angle in place; cos, sin [..., d / 2] broadcast over x's middle axes."""
    if fault == "rope_halves":      # pairs (i, i + d/2), the rotate_half form
        half = x.shape[-1] // 2
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


# the largest finite value of `reduce_precision`'s e4m3 (IEEE-style: the top
# exponent is infinity), (2 - 2^-3) * 2^7
_E4M3_MAX = 240.0


def _fp8_rows(x):
    """x [T, d] rounded to float8 e4m3 (3 mantissa bits, normal down to 2^-6)
    under a per-row absmax scale. `reduce_precision`, not a round trip through
    `float8_e4m3fn`: compiled for the v5e, that round trip left every first
    choice of the control the reference's (0 of 8,192 flipped, PERF.md)."""
    top = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30)
    s = top / _E4M3_MAX
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def routing(z, x, router, bias, variant=None):
    """x [T, h] -> (chosen [T, k] among ALL experts, their weights [T, k]
    normalised over all k chosen)."""
    s = jax.nn.sigmoid(_mm(x, router))
    b = 0.0 if variant == "no_choice_bias" else bias.astype(jnp.float32)
    _, chosen = jax.lax.top_k(s + b, z["per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if z["norm_topk"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if variant != "no_routed_scaling":
        w = w * z["scaling"]
    return chosen, w


def _gated(x, gu_w, down_w):
    gu = _mm(x, gu_w)
    i = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[:, :i]) * gu[:, i:], down_w)


def sparse_ffn(z, x, w, at, variant=None, shared=True):
    """The sparse feed-forward on x [T, h]: the held experts' part (each expert
    on every token, weighted by what the router gave that token for it, 0 where
    it was not chosen) and, with `shared`, the shared expert. The experts'
    leaves come WHOLE, `[sparse layers, E held, ...]`, and this is sparse
    layer `at`: an expert's matrices are read where they lie, one at a time (a
    layer's slice of them, 2.1 GB, would be copied out)."""
    chosen, wts = routing(z, x, w["router"], w["router_bias"], variant)

    def one(total, e):
        weight = jnp.sum(jnp.where(chosen == z["first"] + e, wts, 0.0),
                         axis=-1, keepdims=True)            # [T, 1]
        return total + weight * _gated(x, w["e_gu"][at, e],
                                       w["e_down"][at, e]), None
    total = jnp.zeros(x.shape, jnp.float32)
    if z["held"]:
        total, _ = jax.lax.scan(one, total, jnp.arange(z["held"]))
    if shared:
        total = total + _gated(x, w["s_gu"], w["s_down"])
    return total


def _blocks(fn, x, block):
    """fn over x [T, ...] `block` rows at a time -> [T, ...]."""
    t = x.shape[0]
    out = jax.lax.map(fn, x.reshape((t // block, block) + x.shape[1:]))
    return out.reshape((t,) + out.shape[2:])


def forward(params, z, tokens, first, count, variant=None):
    """tokens [T] int32 (padding after the real ones is harmless: the mask is
    causal; T a multiple of the block). -> logits [count, V] float32 at
    positions first .. first+count-1. `z`: `sizes(cfg)`. `variant`: None, a
    control (CONTROLS) or a planted fault (FAULTS)."""
    nh, dn, dr, dv = z["heads"], z["nope"], z["rope"], z["v"]
    kr, eps, T = z["kv_rank"], z["eps"], tokens.shape[0]
    block = math.gcd(T, BLOCK)
    m = _mscale(z["factor"], z["mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * (1.0 if variant == "no_mscale" else m * m)
    turn = _mscale(z["factor"], z["mscale"]) \
        / _mscale(z["factor"], z["mscale_all_dim"])
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        yarn_frequencies(z, variant), jnp.float32)[None]
    cos, sin = jnp.cos(ang) * turn, jnp.sin(ang) * turn      # [T, dr / 2]
    pos = jnp.arange(T)

    def layer(x, w, at):
        hn = _rms(x, w["ln_attn"].astype(jnp.float32), eps)
        a = _mm(hn, w["wkv_a"])
        c = a[:, :kr] if variant == "no_kv_norm" else \
            _rms(a[:, :kr], w["kv_a_norm"].astype(jnp.float32), eps)
        k_rope = _turn(a[:, kr:], cos, sin, variant)          # [T, dr]
        if variant == "fp8_latent":
            c, k_rope = _fp8_rows(c), _fp8_rows(k_rope)
        cq = _rms(_mm(hn, w["wq_a"]), w["q_a_norm"].astype(jnp.float32), eps)
        wq_b = w["wq_b"].reshape(-1, nh, dn + dr)
        wkv_b = w["wkv_b"].reshape(kr, nh, dn + dv)
        t = T // block
        hg = math.gcd(nh, HEADS)

        def group(g):
            """Heads g * hg .. (g + 1) * hg - 1: their queries, keys
            and values over all T positions, attended `block` queries at a
            time -> [T, hg, dv]."""
            def cut(wt):
                return jax.lax.dynamic_slice_in_dim(wt, g * hg, hg, 1)
            q = _mm(cq, cut(wq_b).reshape(cq.shape[1], -1)).reshape(
                T, hg, dn + dr)
            kv = _mm(c, cut(wkv_b).reshape(kr, -1)).reshape(T, hg,
                                                            dn + dv)
            k_nope, v = kv[..., :dn], kv[..., dn:]

            def attend(blk):
                qb, qpos, cb, sb = blk
                q_rope = _turn(qb[..., dn:], cb[:, None], sb[:, None],
                               variant)
                sc = (jnp.einsum("qhd,khd->hqk", qb[..., :dn], k_nope,
                                 precision="highest")
                      + jnp.einsum("qhd,kd->hqk", q_rope, k_rope,
                                   precision="highest")) * scale
                sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                               -1e30)
                return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1),
                                  v, precision="highest")
            return jax.lax.map(attend, (q.reshape(t, block, hg, -1),
                                        pos.reshape(t, block),
                                        cos.reshape(t, block, -1),
                                        sin.reshape(t, block, -1)))
        o = jax.lax.map(group, jnp.arange(nh // hg))   # [G, t, blk, hg, dv]
        o = o.transpose(1, 2, 0, 3, 4).reshape(T, nh * dv)
        attn = _blocks(lambda u: _mm(u, w["wo"]), o, block)
        x = x + attn.reshape(T, -1)
        hn = _rms(x, w["ln_ffn"].astype(jnp.float32), eps)
        if "w_gu" in w:
            return x + _blocks(lambda u: _gated(u, w["w_gu"], w["w_down"]),
                               hn, block)
        return x + sparse_ffn(z, hn, w, at, variant)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    nd = z["dense_layers"]
    for l in range(z["layers"]):
        names, prefix, at = (DENSE_LEAVES, "d_", l) if l < nd else \
            (SPARSE_LEAVES, "", l - nd)
        x = layer(x, {n: params[prefix + n] if n in EXPERT_LEAVES
                      else params[prefix + n][at] for n in names}, at)
    x = _rms(x, params["norm_f"].astype(jnp.float32), eps)
    xs = jax.lax.dynamic_slice_in_dim(x, first, count, axis=0)
    return _mm(xs, params["unembed"])


def whole_layer(cfg, w, x, first=0, held=None, shared=True):
    """One sparse feed-forward (tests): x [T, h] float32 through the router and
    the experts `first .. first + held - 1` of `w` (a layer's leaves, the
    experts' leaves holding exactly those), with or without the shared
    expert."""
    z = dict(sizes(cfg), first=first)
    if held is not None:
        z["held"] = held
    w = dict(w, e_gu=w["e_gu"][None], e_down=w["e_down"][None])
    with jax.default_matmul_precision("highest"):
        return sparse_ffn(z, x, w, 0, shared=shared)


class Reference:
    def __init__(self, cfg, pad_to, new_tokens, fault=None):
        z = sizes(cfg)
        self.pad_to, self.new = int(pad_to), int(new_tokens)
        z_t = tuple(sorted(z.items()))

        @functools.partial(jax.jit, static_argnames=("variant",))
        def logits(params, tokens, first, variant):
            with jax.default_matmul_precision("highest"):
                return forward(params, dict(z_t), tokens, first, self.new,
                               variant)
        self._logits = logits
        self._fault = fault

    def gaps(self, weights, prompt, served, control=None):
        """-> float array [len(served)]: reference's best logit minus the
        reference's logit of the token judged at each served position: the
        served token, or with `control` (a CONTROLS name) the first choice of
        the forward so bent."""
        n = len(served)
        if n > self.new or len(prompt) + self.new - 1 > self.pad_to:
            raise ValueError("request longer than the reference was sized "
                             "for: %d + %d" % (len(prompt), n))
        toks = np.zeros((self.pad_to,), np.int32)
        toks[:len(prompt)] = prompt
        toks[len(prompt):len(prompt) + n] = served
        first = jnp.int32(len(prompt) - 1)
        ref = np.asarray(self._logits(weights, jnp.asarray(toks), first,
                                      variant=self._fault))[:n]
        judged = np.asarray(served, np.int64)
        if control:
            low = np.asarray(self._logits(weights, jnp.asarray(toks), first,
                                          variant=control))[:n]
            judged = low.argmax(axis=-1)
        return ref.max(axis=-1) - ref[np.arange(n), judged]


def compare(gaps_per_request):
    """-> {name: value} held against LIMITS: the mean gap over every sampled
    served token, and the widest."""
    every = np.concatenate([np.asarray(g, np.float64)
                            for g in gaps_per_request])
    return {"served_logit_gap_mean": float(every.mean()),
            "served_logit_gap": float(every.max())}


# --- what the algorithm NEEDS, from shapes (read by metrics/config_mfu_pct.py,
# metrics/moe_experts_hbm_roofline_pct.py, metrics/step_hbm_roofline_pct.py
# and metrics/latent_attn_*.py) ------------------------------------------------

def _attn_params(z):
    """Matrix parameters of one layer's attention, the published form: q down
    and up, kv down, kv up (applied to every position), o."""
    nh = z["heads"]
    return (z["hidden"] * z["q_rank"] + z["q_rank"] * nh * (z["nope"]
                                                            + z["rope"])
            + z["hidden"] * (z["kv_rank"] + z["rope"])
            + z["kv_rank"] * nh * (z["nope"] + z["v"])
            + nh * z["v"] * z["hidden"])


def _expert_params(z):
    return 3 * z["hidden"] * z["expert_width"]


def request_flops(cfg, prompt_len, new_tokens):
    """Forward FLOPs this chip's share NEEDS to serve one request, the
    published form: every prompt and generated position but the last through
    the stack (2 per matrix parameter it meets; 2 * heads * (nope + rope + v)
    per attended position a layer: the scores over whole heads and the context),
    and the head for the `new_tokens` sampled positions only.

    The routed experts: a token chooses `num_experts_per_tok` of
    `n_routed_experts_published`, of which `n_routed_experts` are held here, so
    the pairs EXPECTED here are per_tok * held / published a token a layer (a
    quarter, at 8 of 384 with 12 held). That expectation is what is counted,
    the same for every seed. The shared expert and the router run for every
    token. Norms, rotary, the sigmoid and the sort are left out."""
    z = sizes(cfg)
    n = prompt_len + new_tokens - 1          # positions run through
    dense, sparse = z["dense_layers"], z["layers"] - z["dense_layers"]
    pairs = z["per_tok"] * z["held"] / z["experts"]
    per_token = (z["layers"] * _attn_params(z)
                 + dense * 3 * z["hidden"] * z["dense_width"]
                 + sparse * (z["hidden"] * z["experts"]
                             + (z["shared"] + pairs) * _expert_params(z)))
    attended = z["layers"] * n * (n + 1) // 2
    return (2 * per_token * n
            + 2 * z["heads"] * (z["nope"] + z["rope"] + z["v"]) * attended
            + 2 * z["hidden"] * z["vocab"] * new_tokens)


def expert_bytes(cfg, experts_touched):
    """Bytes the grouped products NEED from HBM for `experts_touched`
    layer-experts that had at least one token (the program's counter): each
    one's three matrices once."""
    return experts_touched * _expert_params(sizes(cfg)) * WEIGHT_BYTES


def latent_bytes(cfg, rows):
    """Bytes the latent attention NEEDS from HBM to read `rows` cached
    positions once each (the program's `STAT_generation_context_rows`: each
    lane's context once a step, summed over the layers; NOT the per-slot count,
    which counts a prefill chunk's rows once for each of its tokens): one row
    of kv_rank + rope values a position a layer, 1,152 B at the published
    widths. The row's padding to whole lane tiles and the queries are left
    out."""
    z = sizes(cfg)
    return rows * (z["kv_rank"] + z["rope"]) * LATENT_BYTES


def step_bytes(cfg, steps, rows):
    """Bytes `steps` mixed steps NEED from HBM (read by
    metrics/step_hbm_roofline_pct.py): every weight held but the embedding
    once a step, the head included, and the latent rows of `rows` cached
    positions once each (`STAT_generation_context_rows`, which
    `drivers/generation_pool_latent.py` hands the reader as its
    `attended_tokens`: each lane's context once a step, summed over the
    layers). Every held expert is counted, touched or not: a step that
    routes no token to one need not read it, so where some go untouched the
    need is overstated by their share (3 of 48 in `kimi_k2_6_agent_c96`,
    0.26 GB of 9.7). No embedding rows, activations or writes of the new
    rows."""
    weights = sum(int(np.prod(shape))
                  for name, (shape, _) in leaf_shapes(cfg).items()
                  if name != "tok_emb")
    return steps * weights * WEIGHT_BYTES + latent_bytes(cfg, rows)


def latent_flops(cfg, attended):
    """FLOPs of the absorbed attention over `attended` slot-positions (the
    program's `STAT_generation_attended_tokens`: every slot's context, summed
    over the layers): every head's score over the row (kv_rank + rope) and its
    context over the latent (kv_rank), 2 * 64 * (512 + 64 + 512) a position."""
    z = sizes(cfg)
    return attended * 2 * z["heads"] * (2 * z["kv_rank"] + z["rope"])
